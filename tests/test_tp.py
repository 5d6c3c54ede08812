"""Device-sharded KV page pool + mesh-parallel restoration compute
(DESIGN.md §16): byte-identity across tp ∈ {1, 2, 4} through restore,
pause/resume, prefix-sharing CoW and the distributed async store path;
hybrid restoration through the sharded projection pack; planning under
sharding (auto group-size argmin shift, mesh-keyed plan cache, zero
projection recompiles within a bucket); and per-device engine gauges.

``tests/conftest.py`` forces 4 host devices via
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` before jax
imports, so the SPMD path runs on CPU-only CI."""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config.arch import reduced_for_smoke
from repro.config.hardware import PAPER_A100
from repro.configs import get_arch
from repro.core.cost_model import layer_costs, method_times
from repro.core.hcache import HCacheManager
from repro.core.restoration import (choose_group_size, compile_tasks,
                                    projection_trace_count, replay,
                                    s_bucket)
from repro.distributed import tp as tp_lib
from repro.models import Model
from repro.models.module import split
from repro.serving import InferenceEngine, Request
from repro.serving.kv_cache import (PagedBackend, ShardedPagedBackend,
                                    make_backend)
from repro.storage import (AsyncIOEngine, ChunkStore, make_array,
                           make_shards)


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


def fresh_engine(setup, store=None, **kw):
    cfg, model, params = setup
    if store is None:
        store = ChunkStore(make_array("dram", 4), chunk_tokens=16)
    # fp32 storage → pause/restore cycles are lossless and cross-tp
    # equivalence is bit-exact (same convention as test_paged)
    mgr = HCacheManager(model, store, hw=PAPER_A100,
                        schedule_override="hidden", store_dtype=np.float32)
    defaults = dict(max_batch=2, max_seq=128, prefill_chunk=8)
    defaults.update(kw)
    return InferenceEngine(model, params, mgr, **defaults), mgr


def _prompts(cfg, n, seed=7, lo=6, hi=24):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, int(k)).astype(np.int32)
            for k in rng.integers(lo, hi, size=n)]


# ------------------------------------------------------------ TPContext
def test_tp_context_identity_when_single_device():
    one = tp_lib.TPContext(1)
    assert not one.spmd
    x = jnp.arange(8.0).reshape(2, 4)
    assert one.shard_kv(x, 1) is x                 # placement is identity
    assert one.replicate(x) is x
    assert one.kv_sharding(2, 1) is None
    one.validate_heads(3)                          # never raises when off


def test_tp_context_spmd_shardings():
    assert len(jax.devices()) >= 4                 # conftest forced devices
    tp = tp_lib.TPContext(4)
    assert tp.spmd and tp.key() == (4, True)
    with pytest.raises(ValueError, match="n_kv_heads"):
        tp.validate_heads(6)
    tp.validate_heads(8)
    pool = jnp.zeros((2, 8, 16, 4, 8))             # (L, NB, bs, Kv, hd)
    sharded = tp.shard_kv(pool, 3)
    assert len(sharded.sharding.device_set) == 4
    # each device holds a 1-KV-head slice: 1/4 of the bytes
    assert all(s.data.shape == (2, 8, 16, 1, 8)
               for s in sharded.addressable_shards)
    rep = tp.replicate(jnp.arange(4))
    assert len(rep.sharding.device_set) == 4
    # the seam back into unsharded code replicates the head shards
    back = tp.replicate(sharded)
    assert back.sharding.is_fully_replicated
    assert all(s.data.shape == pool.shape for s in back.addressable_shards)


def test_tp_context_raises_when_devices_are_missing():
    """A tp wider than the visible devices is an error, never a silent
    single-device run (conftest forces 4 host devices, so 8 is short)."""
    assert len(jax.devices()) < 8
    with pytest.raises(ValueError, match="tp=8"):
        tp_lib.TPContext(8)


def test_seams_are_identity_without_active_context():
    x = jnp.ones((2, 3, 4))
    assert tp_lib.kv_seam(x, 2) is x
    assert tp_lib.logits_seam(x) is x
    with tp_lib.tp_seam(tp_lib.TPContext(1)):      # tp=1 never activates
        assert tp_lib.active() is None


# ------------------------------------------------- sharded backend state
def test_sharded_backend_pool_layout_and_views(setup):
    cfg, model, params = setup
    tp = tp_lib.TPContext(4)
    b = make_backend("paged", model, 2, 128, tp=tp)
    assert isinstance(b, ShardedPagedBackend)
    # pool sharded over KV heads; page structure replicated
    assert len(b.cache["k_pool"].sharding.device_set) == 4
    assert len(b.cache["block_table"].sharding.device_set) == 4
    total = b.cache["k_pool"].nbytes + b.cache["v_pool"].nbytes
    views = b.device_views()
    assert len(views) == 4
    # every device view sees the same page structure, 1/4 of the bytes
    assert all(v.pool_bytes() == total // 4 for v in views)
    assert all(v.free_count == b.allocator.free_count for v in views)
    rows = b.device_occupancy()
    assert [r["device"] for r in rows] == [0, 1, 2, 3]
    assert all(r["free_pages"] == b.allocator.free_count for r in rows)

    # tp=1 spec degrades to the plain backend with one gauge row
    b1 = make_backend("paged", model, 2, 128, tp=tp_lib.TPContext(1))
    assert type(b1) is PagedBackend
    assert len(b1.device_occupancy()) == 1


def test_sharded_backend_requires_divisible_heads(setup):
    cfg, model, params = setup
    assert cfg.n_kv_heads % 4 == 0                 # the smoke config works
    bad = tp_lib.TPContext(3)
    with pytest.raises(ValueError, match="n_kv_heads"):
        make_backend("paged-tp", model, 2, 128, tp=bad)


# ----------------------------------------------- engine byte-identity
def _run_workload(setup, prompts, tp, **kw):
    eng, _ = fresh_engine(setup, tp=tp, **kw)
    for i, p in enumerate(prompts):
        eng.submit(Request(f"s{i}", p, max_new_tokens=5))
    eng.run()
    out = {f"s{i}": eng.result(f"s{i}") for i in range(len(prompts))}
    met = eng.metrics
    eng.close()
    return out, met


def test_acceptance_workload_byte_identity_across_tp(setup):
    """The paged acceptance workload (8 sessions over 2 slots with
    mid-stream eviction) must produce byte-identical greedy output at
    tp ∈ {1, 2, 4}: every restored token flows through the SPMD grouped
    projection into shard-local pages, every decode through the sharded
    attention with its single logits-seam all-gather."""
    cfg, model, params = setup
    prompts = _prompts(cfg, 8)
    results, metrics = {}, {}
    for tp in (1, 2, 4):
        results[tp], metrics[tp] = _run_workload(
            setup, prompts, tp, max_batch=2, preempt_quantum=3,
            backend="paged")
    assert metrics[4].preemptions > 0              # pause/resume exercised
    assert metrics[4].restored_tokens > 0          # restore wrote pages
    assert results[2] == results[1]
    assert results[4] == results[1]
    # per-device gauges: one row per shard, populated by the run
    assert len(metrics[4].device_gauges) == 4
    assert len(metrics[1].device_gauges) == 1
    assert {r["device"] for r in metrics[4].device_gauges} == {0, 1, 2, 3}


def test_prefix_sharing_cow_byte_identity_under_tp(setup):
    """Cross-session prefix sharing over the sharded pool: adopted
    pages, CoW copies and aliased host chunks are all shard-local ops —
    outputs match the tp=1 sharing run bit for bit."""
    cfg, model, params = setup
    rng = np.random.default_rng(11)
    sys_p = rng.integers(0, cfg.vocab_size, 48).astype(np.int32)
    prompts = [np.concatenate([sys_p, rng.integers(
        0, cfg.vocab_size, 6).astype(np.int32)]) for _ in range(4)]
    results, mets = {}, {}
    for tp in (1, 4):
        results[tp], mets[tp] = _run_workload(
            setup, prompts, tp, backend="paged", prefix_sharing=True)
    assert results[4] == results[1]
    assert mets[4].prefix_hits >= 2                # sharing actually fired
    assert mets[4].dedup_host_bytes > 0


def test_distributed_async_store_byte_identity_under_tp(setup):
    """The full stack at once: striped host shards + async IO engine
    feeding the SPMD projection feeding shard-local pages. Output must
    match the single-device, single-shard DRAM run."""
    cfg, model, params = setup
    prompts = _prompts(cfg, 5, seed=13)

    def sharded_store():
        s = ChunkStore(shards=make_shards(2, 2, "ssd"), chunk_tokens=16)
        s.attach_io_engine(AsyncIOEngine(2))
        return s

    base, bmet = _run_workload(setup, prompts, 1, max_batch=2,
                               preempt_quantum=3, backend="paged")
    got, gmet = _run_workload(setup, prompts, 4, max_batch=2,
                              preempt_quantum=3, backend="paged",
                              store=sharded_store())
    assert gmet.restored_tokens > 0
    assert got == base


# -------------------------------------------------- hybrid + enc-dec
def test_hybrid_restore_byte_identity_under_tp(rules):
    """A hybrid (attention + SSM) session restored through the sharded
    projection pack: attention KV projects SPMD over the mesh, SSM blobs
    bypass it, and the assembled contiguous cache is byte-identical to
    the unsharded restore."""
    cfg = reduced_for_smoke(get_arch("zamba2-2.7b"))
    cfg = dataclasses.replace(cfg, n_heads=4, n_kv_heads=4)
    model = Model(cfg, rules=rules, model_axis=1, dtype=jnp.float32,
                  remat="none")
    params, _ = split(model.init(jax.random.PRNGKey(0)))
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 40), 0,
                              cfg.vocab_size)
    pre = model.prefill(params, {"tokens": toks}, capture_hidden=True)
    caches = {}
    for tp in (1, 4):
        store = ChunkStore(make_array("dram", 4), chunk_tokens=16)
        mgr = HCacheManager(model, store, hw=PAPER_A100,
                            schedule_override="hidden",
                            store_dtype=np.float32)
        mgr.set_tp(tp_lib.TPContext(tp))
        mgr.save_prefill("sess", np.asarray(toks[0]), pre)
        caches[tp] = mgr.restore(params, "sess").cache
        mgr.saver.close()
    assert set(caches[1]) == set(caches[4])
    for key in caches[1]:
        np.testing.assert_array_equal(np.asarray(caches[1][key]),
                                      np.asarray(caches[4][key]), err_msg=key)


def test_encdec_paged_backend_matches_contiguous():
    """Satellite: whisper decoder self-KV through the page pool (cross
    context stays a whole per-slot object) — greedy output identical to
    the contiguous enc-dec backend, including a retire→restore round."""
    from repro.distributed.sharding import default_rules
    from repro.launch.mesh import make_mesh
    from repro.serving.kv_cache import PagedEncDecBackend
    mesh = make_mesh((1, 1), ("data", "model"))
    cfg = reduced_for_smoke(get_arch("whisper-medium"))
    model = Model(cfg, rules=default_rules(mesh), model_axis=1,
                  dtype=jnp.float32, remat="none")
    params, _ = split(model.init(jax.random.PRNGKey(0)))
    rng = np.random.default_rng(9)
    jobs = [(rng.integers(0, cfg.vocab_size, int(n)).astype(np.int32),
             (rng.standard_normal((16, cfg.d_model)) * 0.1)
             .astype(np.float32)) for n in (7, 11, 9)]
    results = {}
    for backend in ("encdec", "paged"):
        store = ChunkStore(make_array("dram", 4), chunk_tokens=16)
        mgr = HCacheManager(model, store, hw=PAPER_A100,
                            schedule_override="hidden",
                            store_dtype=np.float32)
        eng = InferenceEngine(model, params, mgr, max_batch=2, max_seq=96,
                              prefill_chunk=8, backend=backend)
        if backend == "paged":
            assert isinstance(eng.kv, PagedEncDecBackend)
        for i, (p, f) in enumerate(jobs):
            eng.submit(Request(f"w{i}", p, max_new_tokens=5, frames=f))
        eng.run()
        # round 2 on a retired session: self-KV restores into pages
        eng.submit(Request("w0", np.asarray([3], np.int32),
                           max_new_tokens=3))
        eng.run()
        results[backend] = ([eng.result(f"w{i}") for i in range(3)],
                            eng.result("w0"))
        eng.close()
    assert results["paged"] == results["encdec"]


# ---------------------------------------------------- planning under tp
def test_with_mesh_identity_and_pricing():
    assert PAPER_A100.with_mesh(1) is PAPER_A100   # tp=1 changes nothing
    hw4 = PAPER_A100.with_mesh(4)
    assert hw4.mesh_devices == 4
    assert hw4.name.endswith("-tp4")
    cfg = get_arch("llama2-13b")
    t1 = method_times(layer_costs(cfg, 2048)[0], PAPER_A100)
    t4 = method_times(layer_costs(cfg, 2048)[0], hw4)
    # projection compute is divided across the mesh; IO terms are not
    assert t4.c_h == pytest.approx(t1.c_h / 4)
    assert t4.io_h == pytest.approx(t1.io_h)


def test_choose_group_size_argmin_shift_under_mesh():
    """The auto knob re-prices under sharding: with projection compute
    divided 4-ways the dispatch overhead stops being amortizable against
    it, and the replay argmin shifts — the chosen width at tp=4 must
    equal the mesh-priced replay's own argmin, not tp=1's choice."""
    cfg = get_arch("llama2-13b")
    methods = ["hidden"] * cfg.n_layers
    n = 2048

    def span(hw, g):
        times = [method_times(c, hw) for c in layer_costs(cfg, n)]
        ovh = getattr(hw, "dispatch_overhead", 0.0)
        return replay(compile_tasks(tuple(methods), group_size=g), times,
                      dispatch_overhead=ovh).makespan

    cands = (1, 2, 4, 8, cfg.n_layers)
    hw1 = dataclasses.replace(PAPER_A100, dispatch_overhead=2e-3)
    hw4 = hw1.with_mesh(4)
    got1 = choose_group_size(cfg, hw1, n, methods)
    got4 = choose_group_size(cfg, hw4, n, methods)
    assert got1 == min(cands, key=lambda g: (span(hw1, g), -g))
    assert got4 == min(cands, key=lambda g: (span(hw4, g), -g))
    # the regression: mesh pricing must actually reach the argmin — a
    # planner that ignored mesh_devices would return got1 here
    assert got4 != got1


def test_plan_cache_key_includes_mesh(rules):
    """set_tp re-prices the manager and flips the plan-cache key, so
    plans memoized at tp=1 can never leak into the tp=4 pricing."""
    cfg, model, params = _small_lm(rules)
    store = ChunkStore(make_array("dram", 4), chunk_tokens=16)
    mgr = HCacheManager(model, store, hw=PAPER_A100,
                        schedule_override="hidden",
                        store_dtype=np.float32)
    key1 = mgr._price_key()
    mgr.set_tp(tp_lib.TPContext(4))
    assert mgr.hw.mesh_devices == 4
    assert mgr._price_key() != key1
    mgr.set_tp(tp_lib.TPContext(1))
    assert mgr.hw == PAPER_A100                    # with_mesh(1) identity
    assert mgr._price_key() == key1
    mgr.saver.close()


def _small_lm(rules):
    cfg = reduced_for_smoke(get_arch("llama2-7b"))
    model = Model(cfg, rules=rules, model_axis=1, dtype=jnp.float32,
                  remat="none")
    params, _ = split(model.init(jax.random.PRNGKey(0)))
    return cfg, model, params


def test_zero_projection_recompiles_within_bucket_under_tp(rules):
    """DESIGN.md §10's zero-recompile guarantee survives sharding: the
    NamedSharding is a static jit arg, so two same-bucket sessions at
    tp=4 share one compiled SPMD projection."""
    cfg, model, params = _small_lm(rules)
    store = ChunkStore(make_array("dram", 4), chunk_tokens=16)
    mgr = HCacheManager(model, store, hw=PAPER_A100,
                        schedule_override="hidden",
                        store_dtype=np.float32)
    mgr.set_tp(tp_lib.TPContext(4))
    for sid, key, n in (("a", 1, 20), ("b", 2, 28)):
        toks = jax.random.randint(jax.random.PRNGKey(key), (1, n), 0,
                                  cfg.vocab_size)
        pre = model.prefill(params, {"tokens": toks}, capture_hidden=True)
        mgr.save_prefill(sid, np.asarray(toks[0]), pre)
    assert s_bucket(20) == s_bucket(28)
    mgr.restore(params, "a")                 # may trace (fresh bucket+mesh)
    before = projection_trace_count()
    mgr.restore(params, "b")
    assert projection_trace_count() == before, \
        "same-bucket session recompiled the sharded projection"
    mgr.saver.close()


# ----------------------------------------------------------- telemetry
def test_device_gauges_serialize(setup):
    cfg, model, params = setup
    eng, _ = fresh_engine(setup, tp=4, backend="paged")
    eng.submit(Request("g0", _prompts(cfg, 1, seed=21)[0],
                       max_new_tokens=3))
    eng.run()
    m = eng.metrics
    assert len(m.device_gauges) == 4
    for row in m.device_gauges:
        assert {"device", "free_pages", "occupancy_pct",
                "util_pct", "proj_util_pct"} <= set(row)
    blob = json.dumps(m.to_dict())                 # serializable end-to-end
    assert json.loads(blob)["device_gauges"] == m.device_gauges
    eng.close()
