"""Host spans and counters inside the serving program (core/trace.py):
the counters of a save -> evict -> restore round add up to what was
served, saved and restored, and a profiler trace of the engine and the
pump holds every span that ran."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config.arch import reduced_for_smoke
from repro.config.hardware import PAPER_A100
from repro.configs import get_arch
from repro.core import trace as trace_names
from repro.core.hcache import HCacheManager
from repro.frontend import EnginePump
from repro.models import Model
from repro.models.module import split
from repro.serving import InferenceEngine, Request
from repro.storage import ChunkStore, make_array

BATCH = 2


@pytest.fixture(scope="module")
def setup():
    from repro.distributed.sharding import default_rules
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1, 1), ("data", "model"))
    cfg = reduced_for_smoke(get_arch("llama2-7b"))
    model = Model(cfg, rules=default_rules(mesh), model_axis=1,
                  dtype=jnp.float32, remat="none")
    params, _ = split(model.init(jax.random.PRNGKey(0)))
    return cfg, model, params


def engine_for(setup, method="kv"):
    """A paged engine whose manager saves and restores every layer by
    ``method`` (the qwen2-7b cell's plan is all ``kv``)."""
    cfg, model, params = setup
    store = ChunkStore(make_array("dram", 4), chunk_tokens=16)
    mgr = HCacheManager(model, store, hw=PAPER_A100,
                        schedule_override=method, store_dtype=np.float32)
    return InferenceEngine(model, params, mgr, max_batch=BATCH,
                           max_seq=128, prefill_chunk=8, backend="paged")


def serve_round_trip(engine, cfg):
    """Two sessions served and retired (their state saved, their slots
    freed), then the first returns and is restored by kv. Returns the
    prompt tokens served."""
    rng = np.random.default_rng(3)
    lens = {"a": 20, "b": 12}
    for sid, n in lens.items():
        engine.submit(Request(sid, rng.integers(0, cfg.vocab_size, n)
                              .astype(np.int32), max_new_tokens=4))
    engine.run()
    engine.submit(Request("a", rng.integers(0, cfg.vocab_size, 6)
                          .astype(np.int32), max_new_tokens=3))
    engine.run()
    assert engine.sessions["a"].restored
    return sum(lens.values()) + 6


def test_counters_over_save_evict_restore(setup):
    cfg, _, _ = setup
    engine = engine_for(setup)
    mgr = engine.mgr
    n1 = None

    orig = mgr.begin_restore

    def begin(params, sid, *a, **kw):
        nonlocal n1
        n1 = mgr.store.get_manifest(sid)["n_tokens"]
        return orig(params, sid, *a, **kw)
    mgr.begin_restore = begin
    served = serve_round_trip(engine, cfg)
    engine.close()
    m = engine.metrics
    isz = 4                                          # float32 model
    kv_layers = cfg.n_layers
    row = kv_layers * 2 * cfg.n_kv_heads * cfg.head_dim_ * isz

    assert m.admitted == 3
    assert m.queue_wait_sum >= 0.0
    assert m.prefill_tokens == served
    assert m.prefill_wall_sum > 0.0

    assert m.restored_tokens == n1
    assert m.restore_h2d_bytes == n1 * row
    assert m.restore_read_wall > 0.0 and m.restore_upload_wall > 0.0
    assert m.restore_write_wall > 0.0

    stored = sum(mgr.store.get_manifest(s)["n_tokens"] for s in ("a", "b"))
    assert m.saved_tokens == stored
    # prefill chunks and retire tails copy kv rows; every decode step
    # copies the whole batch's hidden states of every layer
    hidden_step = cfg.n_layers * BATCH * cfg.d_model * isz
    assert m.save_d2h_bytes == stored * row + m.decode_steps * hidden_step
    assert m.save_wall_sum > 0.0


def test_trace_holds_the_spans_that_ran(setup, tmp_path):
    from bench.harness import trace as bench_trace
    cfg, _, params = setup
    engine = engine_for(setup)
    hidden = engine_for(setup, method="hidden")
    rng = np.random.default_rng(5)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, 24)))
    pre = hidden.model.prefill(params, {"tokens": toks}, capture_hidden=True)
    hidden.mgr.save_prefill("h", np.asarray(toks[0]), pre)

    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation(bench_trace.WINDOW_SPAN):
            serve_round_trip(engine, cfg)
            hidden.mgr.restore(params, "h")
            pump = EnginePump(engine, idle_wait=0.01).start()
            time.sleep(0.05)                   # the pump idles
            assert pump.call(lambda: 7).result(timeout=60) == 7
            pump.close()
    finally:
        jax.profiler.stop_trace()
        hidden.close()
    tr = bench_trace.load(bench_trace.find_xplane(str(tmp_path)),
                          host_spans=["engine.*"])
    names = {s.name for s in tr.spans}
    assert names == trace_names.NAMES


# the running sums a benchmark reader takes differences of over its window
COUNTERS = ("queue_wait_sum", "admitted", "prefill_wall_sum",
            "prefill_tokens", "save_wall_sum", "saved_tokens",
            "save_d2h_bytes", "restore_read_wall", "restore_upload_wall",
            "restore_write_wall", "restore_h2d_bytes")


@pytest.mark.parametrize("name", COUNTERS)
def test_counter_is_a_scalar_field_in_to_dict(name):
    """A rename of a counter fails here, not as a silently absent
    metric."""
    import dataclasses
    from repro.serving.engine import EngineMetrics
    m = EngineMetrics()
    assert name in {f.name for f in dataclasses.fields(EngineMetrics)}
    setattr(m, name, getattr(m, name) + 3)
    assert m.to_dict()[name] == 3


def test_unknown_span_name_raises():
    with pytest.raises(ValueError, match="engine.nope"):
        trace_names.span("engine.nope")
