"""Serving engine: continuous batching, restoration phase, multi-round
equivalence, crash recovery."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config.arch import reduced_for_smoke
from repro.config.hardware import PAPER_A100
from repro.configs import get_arch
from repro.core.hcache import HCacheManager
from repro.models import Model
from repro.models.module import split
from repro.serving import InferenceEngine, Phase, Request
from repro.storage import ChunkStore, make_array


@pytest.fixture(scope="module")
def setup(rules=None):
    from repro.distributed.sharding import default_rules
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1, 1), ("data", "model"))
    rules = default_rules(mesh)
    cfg = reduced_for_smoke(get_arch("llama2-7b"))
    model = Model(cfg, rules=rules, model_axis=1, dtype=jnp.float32,
                  remat="none")
    params, _ = split(model.init(jax.random.PRNGKey(0)))
    return cfg, model, params


def fresh_engine(setup, **kw):
    cfg, model, params = setup
    store = ChunkStore(make_array("dram", 4), chunk_tokens=16)
    mgr = HCacheManager(model, store, hw=PAPER_A100,
                        schedule_override="hidden")
    defaults = dict(max_batch=2, max_seq=128, prefill_chunk=8)
    defaults.update(kw)
    return InferenceEngine(model, params, mgr, **defaults), mgr


def test_continuous_batching_mixed_lengths(setup):
    cfg, model, params = setup
    engine, _ = fresh_engine(setup)
    rng = np.random.default_rng(0)
    p1 = rng.integers(0, cfg.vocab_size, 20).astype(np.int32)
    p2 = rng.integers(0, cfg.vocab_size, 5).astype(np.int32)
    engine.submit(Request("a", p1, max_new_tokens=6))
    engine.submit(Request("b", p2, max_new_tokens=9))
    engine.run()
    assert len(engine.result("a")) == 6
    assert len(engine.result("b")) == 9


def test_prefill_budget_is_per_step(setup):
    """Sequences prefilling together share ``prefill_chunk`` prompt
    tokens a step: whole chunks, earliest admitted first, a later chunk
    only where it fits, and the answers equal those served alone."""
    cfg, model, params = setup
    rng = np.random.default_rng(23)
    prompts = {"a": rng.integers(0, cfg.vocab_size, 20).astype(np.int32),
               "b": rng.integers(0, cfg.vocab_size, 3).astype(np.int32),
               "c": rng.integers(0, cfg.vocab_size, 4).astype(np.int32)}
    alone = {}
    for sid, p in prompts.items():
        e, _ = fresh_engine(setup, max_batch=3)
        e.submit(Request(sid, p, max_new_tokens=3))
        e.run()
        alone[sid] = e.result(sid)
    engine, _ = fresh_engine(setup, max_batch=3)
    for sid, p in prompts.items():
        engine.submit(Request(sid, p, max_new_tokens=3))
    per_step = []
    while any(s is not None for s in engine.slots) or engine.queue:
        before = engine.metrics.prefill_tokens
        engine.step()
        per_step.append(engine.metrics.prefill_tokens - before)
    # a: chunks of 8, 8, 4; b and c fit beside a's 4-token tail only
    assert per_step[:3] == [8, 8, 4 + 3]
    assert all(n <= 8 for n in per_step)
    assert sum(per_step) == sum(len(p) for p in prompts.values())
    for sid in prompts:
        assert engine.result(sid) == alone[sid]


def test_multi_round_restoration_matches_no_eviction(setup):
    """Round-2 generation after evict+restore == never-evicted decoding."""
    cfg, model, params = setup
    engine, _ = fresh_engine(setup)
    rng = np.random.default_rng(1)
    p1 = rng.integers(0, cfg.vocab_size, 18).astype(np.int32)
    engine.submit(Request("alice", p1, max_new_tokens=5))
    engine.run()
    g1 = engine.result("alice")
    p2 = rng.integers(0, cfg.vocab_size, 7).astype(np.int32)
    engine.submit(Request("alice", p2, max_new_tokens=4))
    engine.run()
    g2 = engine.result("alice")

    # ground truth: single prefill over the whole history
    full = np.concatenate([p1, np.asarray(g1[:-1], np.int32), p2])
    pre = model.prefill(params, {"tokens": jnp.asarray(full)[None]})
    n = len(full)
    k = jnp.pad(pre["kv"][0], ((0, 0), (0, 0), (0, 128 - n), (0, 0), (0, 0)))
    v = jnp.pad(pre["kv"][1], ((0, 0), (0, 0), (0, 128 - n), (0, 0), (0, 0)))
    cache = {"k": k, "v": v, "lengths": jnp.asarray([n], jnp.int32)}
    nt = jnp.argmax(pre["logits"][:, -1], -1).astype(jnp.int32)[:, None]
    want = []
    for _ in range(4):
        want.append(int(nt[0, 0]))
        lg, cache = model.decode_step(params, cache, nt)
        nt = jnp.argmax(lg[:, -1], -1).astype(jnp.int32)[:, None]
    assert g2 == want


def test_crash_recovery_resumes_sessions(setup):
    """A fresh engine over the same store restores evicted sessions —
    serving fault tolerance IS HCache."""
    cfg, model, params = setup
    engine, mgr = fresh_engine(setup)
    rng = np.random.default_rng(2)
    p1 = rng.integers(0, cfg.vocab_size, 12).astype(np.int32)
    engine.submit(Request("carol", p1, max_new_tokens=5))
    engine.run()
    g1 = engine.result("carol")

    engine2 = InferenceEngine(model, params, mgr, max_batch=2, max_seq=128,
                              prefill_chunk=8)     # "restarted" process
    assert "carol" in engine2.recoverable_sessions()
    p2 = rng.integers(0, cfg.vocab_size, 6).astype(np.int32)
    engine2.submit(Request("carol", p2, max_new_tokens=3))
    engine2.run()
    assert len(engine2.result("carol")) == 3
    assert engine2.sessions["carol"].history_len == 12 + 5 - 1 > 0


def test_metrics_populated(setup):
    cfg, model, params = setup
    engine, _ = fresh_engine(setup)
    p = np.arange(10, dtype=np.int32) % cfg.vocab_size
    engine.submit(Request("m", p, max_new_tokens=4))
    engine.run()
    assert len(engine.metrics.ttft_wall) == 1
    assert engine.metrics.decode_steps >= 3
    assert engine.metrics.ttft_wall[0] > 0
