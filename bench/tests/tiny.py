"""Small versions of the benchmark's cells for CPU tests: the real
configuration and traffic files with their widths, depths and lengths
shrunk, everything else as committed."""
from __future__ import annotations

import contextlib
import copy

from bench.harness.cell import load_cell

TINY_QWEN = dict(hidden_size=64, intermediate_size=128,
                 num_attention_heads=4, num_key_value_heads=2,
                 num_hidden_layers=2, vocab_size=384)
TINY_OPT = dict(hidden_size=64, ffn_dim=128, num_attention_heads=4,
                num_hidden_layers=2, vocab_size=384)


def shrink_config(cfg: dict) -> dict:
    cfg = copy.deepcopy(cfg)
    c, ov = cfg["config"], cfg["program"]["overrides"]
    if c["model_type"] == "qwen2":
        c.update(TINY_QWEN)
        ov.update(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
                  vocab_size=384)
    else:
        c.update(TINY_OPT)
        ov.update(n_layers=2, d_model=64, n_heads=4, n_kv_heads=4, d_ff=128,
                  vocab_size=384)
    cfg["program"].update(max_batch=4, cache_blocks=400,
                          max_seq=min(cfg["program"]["max_seq"], 2048))
    # the limit scales with the logits' spread: a tied head over 64-wide
    # states gives logits of std ~0.16 (~1.7 at opt-30b's 7168). Readings
    # at this size on the CPU: program at most 0.008 (qwen2) and 0.0025
    # (opt); float8 control at least 0.14 (qwen2) and 0.0135 (opt, over
    # a 6 s window)
    cfg["check"]["max_logit_gap"] = (0.1 if c["model_type"] == "qwen2"
                                     else 0.006)
    return cfg


# cells whose files are committed but which BENCHMARK.json does not list
# yet; the CPU tests keep their configuration and traffic running through
# the harness
LATER = {"qwen2-7b.fresh": {"config": "qwen2-7b", "traffic": "fresh_2k",
                            "chips": 1},
         "opt-30b.returning": {"config": "opt-30b",
                               "traffic": "returning_2k", "chips": 1}}


def full_cell(name: str):
    """A cell at its committed sizes, listed or not."""
    return load_cell(name, workload=LATER.get(name))


def tiny_cell(name: str, *, sessions: int = 3, rate: float = 1.5):
    cell = full_cell(name)
    cell.cfg = shrink_config(cell.cfg)
    m = cell.mix
    if m["kind"] == "returning":
        m.update(sessions=sessions, history_tokens=[256, 384],
                 user_tokens=[16, 48], answer_tokens=[32, 64],
                 rate_per_s=rate, min_gap_s=1.0,
                 max_transcript=min(m["max_transcript"], 1024),
                 check_requests=2)
    else:
        m.update(prompt_tokens=[128, 256], answer_tokens=[32, 64],
                 rate_per_s=rate, check_requests=2)
    return cell


@contextlib.contextmanager
def full_size_plan(full_cfg: dict):
    """Make the manager plan a small model's layers the way it plans the
    full-size configuration on a v5e (the restore methods depend on the
    widths: at a small size the planner would recompute everything)."""
    import jax.numpy as jnp

    from bench.harness import stack
    from repro.config.hardware import TPU_V5E
    from repro.core.hcache import HCacheManager
    from repro.core.scheduler import Schedule
    from repro.distributed.sharding import default_rules
    from repro.launch.mesh import make_mesh
    from repro.models import Model
    from repro.storage import ChunkStore, make_array

    arch, _ = stack.program_arch(full_cfg)
    model = Model(arch, rules=default_rules(make_mesh((1, 1),
                                                      ("data", "model"))),
                  model_axis=1, dtype=jnp.bfloat16, remat="none")
    full = HCacheManager(model, ChunkStore(make_array("ssd", 1)),
                         hw=TPU_V5E)
    orig = HCacheManager.plan

    def plan(self, n_tokens):
        want = orig(full, n_tokens).methods
        L = self.cfg.n_layers
        methods = tuple(want[i * len(want) // L] for i in range(L))
        return Schedule(methods, 0.0, 0.0, 0.0, 0.0)
    HCacheManager.plan = plan
    try:
        yield
    finally:
        HCacheManager.plan = orig
