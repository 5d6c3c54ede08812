"""Pipelined restoration executor: task-graph compilation, one-source-of-
truth timelines, incremental engine-integrated restoration (restore-
equivalence + decode-isolation), and prefetch."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config.arch import reduced_for_smoke
from repro.config.hardware import PAPER_A100
from repro.configs import get_arch
from repro.core.cost_model import layer_costs, method_times
from repro.core.hcache import HCacheManager
from repro.core.pipeline import simulate
from repro.core.restoration import (CacheAssembler, RestorationExecutor,
                                    compile_tasks, replay)
from repro.core.scheduler import solve
from repro.models import Model
from repro.models.module import split
from repro.serving import InferenceEngine, Phase, Request
from repro.storage import ChunkStore, make_array


# ------------------------------------------------------------- task graph
def test_compile_tasks_orders_streams():
    """IO: hidden fetches first (layer order), then kv; compute: recompute
    prefix then projections; every projection depends on its fetch."""
    methods = ["recompute", "hidden", "kv", "hidden"]
    tasks = compile_tasks(methods)
    kinds = [(t.kind, t.layer) for t in tasks]
    assert kinds == [("io_h", 1), ("io_h", 3), ("io_kv", 2),
                     ("recompute", 0), ("project", 1), ("project", 3)]
    for t in tasks:
        if t.kind == "project":
            dep = tasks[t.dep]
            assert dep.kind == "io_h" and dep.layer == t.layer


def test_replay_is_simulate():
    """pipeline.simulate IS a replay of the compiled task graph — any
    schedule, any model: one source of truth."""
    cfg = get_arch("llama2-13b")
    for n in (512, 4096):
        sched = solve(cfg, n, PAPER_A100)
        times = [method_times(c, PAPER_A100) for c in layer_costs(cfg, n)]
        for methods in (sched.methods, ["kv"] * cfg.n_layers,
                        ["hidden"] * cfg.n_layers):
            a = simulate(methods, times)
            b = replay(compile_tasks(methods), times)
            assert a == b


def test_replay_order_invariant_per_stream():
    """Interleaving the two streams differently (as incremental execution
    does) never changes the timeline, as long as per-stream order holds."""
    cfg = get_arch("llama2-7b")
    sched = solve(cfg, 2048, PAPER_A100)
    times = [method_times(c, PAPER_A100)
             for c in layer_costs(cfg, 2048)]
    tasks = compile_tasks(sched.methods)
    io = [i for i, t in enumerate(tasks) if t.stream == "io"]
    comp = [i for i, t in enumerate(tasks) if t.stream == "compute"]
    # perfect round-robin interleave of the two streams
    order = []
    while io or comp:
        if io:
            order.append(io.pop(0))
        if comp:
            order.append(comp.pop(0))
    assert replay(tasks, times, order) == replay(tasks, times)


# --------------------------------------------------------------- fixtures
@pytest.fixture(scope="module")
def setup():
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


# ------------------------------------------------- incremental execution
def test_executor_incremental_matches_run_to_completion(setup):
    """Stepping the executor 1 task at a time produces the same cache and
    the same timeline as running it in one go."""
    cfg, model, params = setup
    _, mgr = fresh_engine(setup)
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 24), 0,
                              cfg.vocab_size)
    pre = model.prefill(params, {"tokens": toks}, capture_hidden=True)
    mgr.save_prefill("s", np.asarray(toks[0]), pre)

    whole = mgr.restore(params, "s")
    sink = CacheAssembler(model)
    ex = RestorationExecutor(mgr, params, "s", sink=sink)
    n_steps = 0
    while not ex.step(max_tasks=1):
        n_steps += 1
    assert n_steps >= len(ex.tasks) - 1          # genuinely incremental
    np.testing.assert_array_equal(np.asarray(sink.cache["k"]),
                                  np.asarray(whole.cache["k"]))
    assert ex.timeline() == whole.timeline


def test_engine_restore_equivalence_logits(setup):
    """(a) A session restored mid-conversation through the incremental
    executor produces decode logits matching an uninterrupted session."""
    cfg, model, params = setup
    engine, _ = fresh_engine(setup)
    rng = np.random.default_rng(7)
    p1 = rng.integers(0, cfg.vocab_size, 20).astype(np.int32)
    engine.submit(Request("eq", p1, max_new_tokens=5))
    engine.run()
    g1 = engine.result("eq")
    p2 = rng.integers(0, cfg.vocab_size, 6).astype(np.int32)
    engine.submit(Request("eq", p2, max_new_tokens=1))
    engine.run()
    assert engine.sessions["eq"].restored

    # uninterrupted reference: one prefill over the whole history
    full = np.concatenate([p1, np.asarray(g1[:-1], np.int32), p2])
    pre = model.prefill(params, {"tokens": jnp.asarray(full)[None]})
    want = int(jnp.argmax(pre["logits"][:, -1], -1)[0])
    assert engine.result("eq") == [want]


def test_decode_isolation_while_restoring(setup):
    """(b) An actively decoding session emits a token on every engine step
    while another session is in Phase.RESTORING — restoration never
    blocks the decode batch."""
    cfg, model, params = setup
    engine, mgr = fresh_engine(setup, restore_tasks_per_step=1)
    rng = np.random.default_rng(8)
    # store state for "warm" so its admission goes through RESTORING
    p0 = rng.integers(0, cfg.vocab_size, 30).astype(np.int32)
    engine.submit(Request("warm", p0, max_new_tokens=2))
    engine.run()

    engine.submit(Request("active", rng.integers(
        0, cfg.vocab_size, 8).astype(np.int32), max_new_tokens=40))
    for _ in range(3):
        engine.step()                      # "active" reaches DECODE
    active = engine.sessions["active"]
    assert active.phase == Phase.DECODE

    engine.submit(Request("warm", rng.integers(
        0, cfg.vocab_size, 4).astype(np.int32), max_new_tokens=2))
    engine.step()
    warm = engine.sessions["warm"]
    assert warm.phase == Phase.RESTORING   # multi-step phase, 1 task/step
    restoring_steps = 0
    while warm.phase == Phase.RESTORING:
        before = len(active.generated)
        engine.step()
        restoring_steps += 1
        assert len(active.generated) == before + 1, \
            "decode batch stalled during restoration"
    assert restoring_steps >= 2            # restoration really spanned steps


def test_two_sessions_restore_concurrently(setup):
    """≥2 sessions interleave their restorations with an active workload."""
    cfg, model, params = setup
    engine, mgr = fresh_engine(setup, max_batch=3, restore_tasks_per_step=1)
    rng = np.random.default_rng(9)
    prompts = {}
    for sid in ("a", "b"):
        prompts[sid] = rng.integers(0, cfg.vocab_size, 16).astype(np.int32)
        engine.submit(Request(sid, prompts[sid], max_new_tokens=2))
    engine.run()
    for sid in ("a", "b"):
        engine.submit(Request(sid, rng.integers(
            0, cfg.vocab_size, 4).astype(np.int32), max_new_tokens=2))
    engine.step()
    phases = {sid: engine.sessions[sid].phase for sid in ("a", "b")}
    assert phases == {"a": Phase.RESTORING, "b": Phase.RESTORING}
    engine.run()
    assert engine.sessions["a"].restored and engine.sessions["b"].restored
    assert len(engine.result("a")) == 2 and len(engine.result("b")) == 2


def test_prefetch_starts_before_slot_frees(setup):
    """A queued session with stored state gets IO prefetched while all
    slots are still busy."""
    cfg, model, params = setup
    engine, mgr = fresh_engine(setup, max_batch=1, restore_tasks_per_step=2)
    rng = np.random.default_rng(10)
    p = rng.integers(0, cfg.vocab_size, 20).astype(np.int32)
    engine.submit(Request("pre", p, max_new_tokens=2))
    engine.run()

    # occupy the only slot, then queue the stored session behind it
    engine.submit(Request("hog", rng.integers(
        0, cfg.vocab_size, 8).astype(np.int32), max_new_tokens=30))
    engine.step()
    engine.submit(Request("pre", rng.integers(
        0, cfg.vocab_size, 4).astype(np.int32), max_new_tokens=2))
    engine.step()
    assert "pre" in engine._prefetch
    warm = engine._prefetch["pre"]
    assert len(warm.executed) >= 1         # layer-0 IO already issued
    assert all(warm.tasks[i].stream == "io" for i in warm.executed)
    engine.run()
    assert engine.sessions["pre"].restored
    assert len(engine.result("pre")) == 2


def test_stale_prefetch_discarded_on_manifest_change(setup):
    """A prefetch executor warmed from an older manifest is discarded at
    admission when the session saved more state in between (e.g. its
    previous turn retired after the prefetch started)."""
    cfg, model, params = setup
    engine, mgr = fresh_engine(setup, max_batch=1, restore_tasks_per_step=4)
    rng = np.random.default_rng(12)
    p1 = rng.integers(0, cfg.vocab_size, 14).astype(np.int32)
    engine.submit(Request("st", p1, max_new_tokens=3))
    engine.run()
    n1 = mgr.store.get_manifest("st")["n_tokens"]

    # warm a (soon-stale) executor from the current manifest by hand
    engine._prefetch["st"] = mgr.begin_restore(params, "st")
    engine._prefetch["st"].prefetch_step(1)

    # the session grows: another turn runs and retires
    p2 = rng.integers(0, cfg.vocab_size, 5).astype(np.int32)
    engine.submit(Request("st", p2, max_new_tokens=3))
    engine.run()
    n2 = mgr.store.get_manifest("st")["n_tokens"]
    assert n2 > n1

    p3 = rng.integers(0, cfg.vocab_size, 4).astype(np.int32)
    engine.submit(Request("st", p3, max_new_tokens=2))
    engine.run()
    assert engine.sessions["st"].history_len == n2   # not the stale n1


def test_engine_reports_measured_io_on_ssd(setup):
    """With simulated-SSD devices the executor's striped reads, uploads
    and cache writes surface as measured seconds and bytes in the engine
    metrics."""
    cfg, model, params = setup
    from repro.config.hardware import PAPER_A100 as hw
    store = ChunkStore(make_array("ssd", 4), chunk_tokens=16)
    mgr = HCacheManager(model, store, hw=hw, schedule_override="hidden")
    engine = InferenceEngine(model, params, mgr, max_batch=2, max_seq=128,
                             prefill_chunk=8)
    rng = np.random.default_rng(13)
    p = rng.integers(0, cfg.vocab_size, 20).astype(np.int32)
    engine.submit(Request("io", p, max_new_tokens=2))
    engine.run()
    engine.submit(Request("io", rng.integers(
        0, cfg.vocab_size, 4).astype(np.int32), max_new_tokens=2))
    engine.run()
    m = engine.metrics
    assert m.restore_read_wall > 0 and m.restore_upload_wall > 0
    assert m.restore_write_wall > 0
    # every restored token's hidden row of every layer went to the device
    # (one group upload, padded to the S-bucket)
    assert m.restore_h2d_bytes >= (m.restored_tokens * cfg.n_layers
                                   * cfg.d_model * 2)


def test_metrics_ttft_populations(setup):
    """Restored TTFT and restore counters are recorded only for sessions
    that actually restored; cold starts land in their own population."""
    cfg, model, params = setup
    engine, _ = fresh_engine(setup)
    rng = np.random.default_rng(11)
    engine.submit(Request("cold", rng.integers(
        0, cfg.vocab_size, 10).astype(np.int32), max_new_tokens=2))
    engine.run()
    assert len(engine.metrics.ttft_wall_cold) == 1
    assert engine.metrics.ttft_wall_restored == []
    assert engine.metrics.restored_tokens == 0
    assert engine.metrics.restore_wall_sum == 0.0

    engine.submit(Request("cold", rng.integers(
        0, cfg.vocab_size, 4).astype(np.int32), max_new_tokens=2))
    engine.run()
    assert len(engine.metrics.ttft_wall_restored) == 1
    assert engine.metrics.ttft_wall_restored[0] > 0
    assert engine.metrics.restored_tokens > 0
    assert engine.metrics.restore_wall_sum > 0
    assert len(engine.metrics.ttft_wall_cold) == 1
