"""Paged KV-cache backend: greedy equivalence vs contiguous (restore,
mid-stream pause/resume, retire), the paged decode step (against the
contiguous step and a full forward, on the gather path and on the Pallas
kernel; donation; sentinel writes), allocator edge cases (exhaustion ->
queue backpressure, page reuse after eviction), occupancy gauges."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config.arch import reduced_for_smoke
from repro.config.hardware import PAPER_A100
from repro.configs import get_arch
from repro.core.hcache import HCacheManager
from repro.models import Model
from repro.serving import InferenceEngine, Request
from repro.serving.kv_cache import (BlockAllocator, ContiguousBackend,
                                    PagedBackend, make_backend)
from repro.storage import ChunkStore, make_array


@pytest.fixture(scope="module")
def setup():
    from repro.distributed.sharding import default_rules
    from repro.launch.mesh import make_mesh
    from repro.models.module import split
    mesh = make_mesh((1, 1), ("data", "model"))
    cfg = reduced_for_smoke(get_arch("llama2-7b"))
    model = Model(cfg, rules=default_rules(mesh), model_axis=1,
                  dtype=jnp.float32, remat="none")
    params, _ = split(model.init(jax.random.PRNGKey(0)))
    return cfg, model, params


def fresh_engine(setup, **kw):
    cfg, model, params = setup
    store = ChunkStore(make_array("dram", 4), chunk_tokens=16)
    # store_dtype matches the model dtype so pause/restore cycles are
    # lossless and cross-backend equivalence is bit-exact
    mgr = HCacheManager(model, store, hw=PAPER_A100,
                        schedule_override="hidden", store_dtype=np.float32)
    defaults = dict(max_batch=2, max_seq=128, prefill_chunk=8)
    defaults.update(kw)
    return InferenceEngine(model, params, mgr, **defaults), mgr


def _prompts(cfg, n, seed=7, lo=6, hi=24):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, int(k)).astype(np.int32)
            for k in rng.integers(lo, hi, size=n)]


# ----------------------------------------------------------- allocator
def test_block_allocator_edges():
    a = BlockAllocator(4)
    got = a.alloc(3)
    assert len(got) == 3 and a.free_count == 1
    assert a.alloc(2) is None                 # exhaustion: no partial grant
    assert a.free_count == 1
    last = a.alloc(1)
    assert a.alloc(1) is None and a.free_count == 0
    a.free(got)
    assert a.free_count == 3
    # LIFO reuse: the next alloc hands back the just-freed pages
    assert a.alloc(3) == got
    a.free(last)
    assert a.free_count == 1


def test_paged_backend_rejects_non_lm():
    from repro.distributed.sharding import default_rules
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1, 1), ("data", "model"))
    cfg = reduced_for_smoke(get_arch("falcon-mamba-7b"))
    ssm = Model(cfg, rules=default_rules(mesh), model_axis=1,
                dtype=jnp.float32, remat="none")
    with pytest.raises(NotImplementedError):
        make_backend("paged", ssm, 2, 128)


# --------------------------------------------------------- equivalence
def test_paged_equivalence_restore_pause_retire(setup):
    """The acceptance workload: 8 sessions over 2 slots with mid-stream
    eviction — every session retires, pauses, and restores through the
    paged layout with byte-identical greedy output to contiguous."""
    cfg, model, params = setup
    prompts = _prompts(cfg, 8)
    results, metrics = {}, {}
    for backend in ("contiguous", "paged"):
        eng, _ = fresh_engine(setup, max_batch=2, preempt_quantum=3,
                              backend=backend)
        for i, p in enumerate(prompts):
            eng.submit(Request(f"s{i}", p, max_new_tokens=5))
        eng.run()
        results[backend] = {f"s{i}": eng.result(f"s{i}") for i in range(8)}
        metrics[backend] = eng.metrics
        eng.close()
    assert metrics["paged"].preemptions > 0        # pause/resume exercised
    assert metrics["paged"].restored_tokens > 0    # restore wrote pages
    assert results["paged"] == results["contiguous"]
    # same memory (2 slots worth): paged reserves per-session need only
    assert (metrics["paged"].reserved_tokens_peak
            < metrics["contiguous"].reserved_tokens_peak)
    assert (metrics["paged"].occupancy_mean
            > metrics["contiguous"].occupancy_mean)


def test_paged_multi_round_restoration_matches_ground_truth(setup):
    """Round-2 generation after retire + paged restoration == a single
    prefill over the whole history (same idiom as the contiguous test in
    test_serving.py — here the restored KV lands in scattered pages)."""
    cfg, model, params = setup
    eng, _ = fresh_engine(setup, backend="paged")
    rng = np.random.default_rng(1)
    p1 = rng.integers(0, cfg.vocab_size, 18).astype(np.int32)
    eng.submit(Request("alice", p1, max_new_tokens=5))
    eng.run()
    g1 = eng.result("alice")
    p2 = rng.integers(0, cfg.vocab_size, 7).astype(np.int32)
    eng.submit(Request("alice", p2, max_new_tokens=4))
    eng.run()
    g2 = eng.result("alice")
    eng.close()

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


# ---------------------------------------------------- the decode step
@pytest.fixture(params=["gather", "kernel"])
def decode_path(request, monkeypatch):
    """Which branch the paged step takes: off the TPU it gathers each
    layer's logical layout; ``kernel`` steers it onto its TPU branch,
    the Pallas kernel, run in interpret mode. Traces made under the
    steer are dropped afterwards, so no later step reuses them."""
    traced = []
    if request.param == "kernel":
        from repro.kernels import ops
        kernel = ops.paged_decode_attention_pallas

        def interpreted(*a, **k):
            traced.append(1)
            return kernel(*a, **dict(k, interpret=True))
        monkeypatch.setattr(ops, "on_tpu", lambda: True)
        monkeypatch.setattr(ops, "paged_decode_attention_pallas",
                            interpreted)
    jax.clear_caches()
    yield request.param
    jax.clear_caches()
    assert bool(traced) == (request.param == "kernel")


def _paged_twin(setup, prompts, *, bs=16, MB=8, seed=0):
    """The prompts prefilled into a contiguous cache and into a paged one
    whose pages are permuted through a junk-filled pool, with sentinel
    table entries past the pages each slot can reach."""
    cfg, model, params = setup
    rng = np.random.default_rng(seed)
    B, S = len(prompts), MB * bs
    L, Kv, hd = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim_
    NB = B * MB + 5
    cont = model.init_cache(B, S)
    pools = [rng.normal(size=(L, NB, bs, Kv, hd)).astype(np.float32)
             for _ in range(2)]
    table = np.full((B, MB), NB, np.int32)
    perm = rng.permutation(NB)
    first = []
    for b, p in enumerate(prompts):
        pre = model.prefill(params, {"tokens": jnp.asarray(p)[None]})
        n = len(p)
        for name, kv, pool in zip(("k", "v"), pre["kv"], pools):
            cont[name] = cont[name].at[:, b, :n].set(kv[:, 0])
            for j in range(-(-n // bs)):
                pool[:, perm[b * MB + j], :min(bs, n - j * bs)] = \
                    np.asarray(kv[:, 0, j * bs:(j + 1) * bs])
        table[b, :MB - 1] = perm[b * MB:b * MB + MB - 1]
        first.append(int(jnp.argmax(pre["logits"][0, -1])))
    lengths = jnp.asarray([len(p) for p in prompts], jnp.int32)
    cont["lengths"] = lengths
    paged = {"k_pool": jnp.asarray(pools[0]), "v_pool": jnp.asarray(pools[1]),
             "block_table": jnp.asarray(table), "lengths": lengths}
    return cont, paged, np.asarray(first, np.int32)[:, None]


def test_paged_step_matches_contiguous_and_full_forward(setup, decode_path):
    """Four steps of two slots (37 tokens, and exactly one page) through
    the paged step, against the contiguous step and against a float32
    forward over each whole sequence, whose last position is the new
    token attending to itself. The gather path is the contiguous step's
    arithmetic and matches it bitwise; the kernel sums in another order
    and stays within 1e-4 of both."""
    cfg, model, params = setup
    rng = np.random.default_rng(8)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (37, 16)]
    cont, paged, tok = _paged_twin(setup, prompts)
    cont_step = jax.jit(lambda p, c, t: model.decode_step(p, c, t))
    paged_step = jax.jit(lambda p, c, t: model.decode_step_paged(p, c, t))
    seqs = [list(p) for p in prompts]
    for _ in range(4):
        for b in range(2):
            seqs[b].append(int(tok[b, 0]))
        lg_c, cont = cont_step(params, cont, jnp.asarray(tok))
        lg_p, paged, _ = paged_step(params, paged, jnp.asarray(tok))
        if decode_path == "gather":
            np.testing.assert_array_equal(np.asarray(lg_p), np.asarray(lg_c))
        else:
            np.testing.assert_allclose(np.asarray(lg_p), np.asarray(lg_c),
                                       rtol=1e-4, atol=1e-4)
        for b in range(2):
            full = model.prefill(params, {"tokens": jnp.asarray(seqs[b])[None]})
            np.testing.assert_allclose(np.asarray(lg_p[b, 0]),
                                       np.asarray(full["logits"][0, -1]),
                                       rtol=1e-4, atol=1e-4)
        tok = np.asarray(jnp.argmax(lg_c[:, 0], -1), np.int32)[:, None]
    np.testing.assert_array_equal(np.asarray(paged["lengths"]),
                                  [37 + 4, 16 + 4])


def test_decode_donates_the_pool(setup):
    """The backend's step takes the cache as donated: the pools it was
    handed are gone after the step, and the step's own are live."""
    cfg, model, params = setup
    b = PagedBackend(model, max_batch=2, max_seq=64, block_size=16)
    assert b.reserve(0, 20)
    b.set_length(0, 5)
    old = (b.cache["k_pool"], b.cache["v_pool"])
    b.decode(params, jnp.zeros((2, 1), jnp.int32))
    assert all(x.is_deleted() for x in old)
    assert not any(b.cache[n].is_deleted() for n in ("k_pool", "v_pool"))
    assert b.get_lengths().tolist() == [6, 1]


def test_sentinel_writes_leave_the_pool_unchanged(setup, decode_path):
    """A slot whose table is full and an unoccupied slot (a sentinel row)
    both write their new token to a sentinel address: the step drops
    both writes and the pool comes back as it went in."""
    cfg, model, params = setup
    b = PagedBackend(model, max_batch=2, max_seq=64, block_size=16)
    assert b.reserve(0, 64)                        # the whole table row
    rng = np.random.default_rng(6)
    for name in ("k_pool", "v_pool"):
        b.cache[name] = jnp.asarray(
            rng.normal(size=b.cache[name].shape).astype(np.float32))
    b.set_length(0, 64)
    before = {n: np.asarray(b.cache[n]) for n in ("k_pool", "v_pool")}
    b.decode(params, jnp.zeros((2, 1), jnp.int32))
    for n, want in before.items():
        np.testing.assert_array_equal(np.asarray(b.cache[n]), want)


# ------------------------------------------- exhaustion / backpressure
def test_pool_exhaustion_backpressures_queue_and_reuses_pages(setup):
    """A 4-page pool (64 tokens) under 4 slots and 6 two-page sessions:
    admission stalls on the allocator (free slots exist, pages don't),
    sessions run anyway as pages recycle, and after drain every page is
    back in the free list."""
    cfg, model, params = setup
    eng, _ = fresh_engine(setup, max_batch=4, backend="paged",
                          cache_blocks=4)
    prompts = _prompts(cfg, 6, seed=3, lo=16, hi=24)
    for i, p in enumerate(prompts):
        eng.submit(Request(f"b{i}", p, max_new_tokens=3))
    eng.run()
    assert all(len(eng.result(f"b{i}")) == 3 for i in range(6))
    m = eng.metrics
    assert m.alloc_stalls > 0                      # pool gated admission
    assert m.concurrent_peak < 4                   # slots alone didn't
    assert eng.kv.allocator.free_count == 4        # page reuse: all back
    assert all(not blks for blks in eng.kv.slot_blocks)
    eng.close()


def test_reserve_is_all_or_nothing(setup):
    cfg, model, params = setup
    b = PagedBackend(model, max_batch=2, max_seq=64, block_size=16,
                     num_blocks=3)
    assert b.reserve(0, 40)                        # 3 pages
    assert b.allocator.free_count == 0
    assert not b.can_reserve(1)
    assert not b.reserve(1, 1)                     # exhausted: no grant
    assert b.allocator.free_count == 0             # and nothing leaked
    b.free_slot(0)
    assert b.allocator.free_count == 3
    assert b.reserve(1, 1)                         # freed pages reusable


def test_reserve_clamps_overlong_sessions_to_table_row(setup):
    """A worst-case need past max_seq (or the pool) clamps to one full
    table row instead of crashing the table write or wedging admission —
    matching contiguous, where overflow decode writes silently drop."""
    cfg, model, params = setup
    b = PagedBackend(model, max_batch=2, max_seq=64, block_size=16)
    assert b.can_reserve(100_000)
    assert b.reserve(0, 100_000)
    assert len(b.slot_blocks[0]) == 4              # blocks_per_seq, not 6250
    assert b.allocator.free_count == 4

    tiny = PagedBackend(model, max_batch=2, max_seq=64, block_size=16,
                        num_blocks=2)              # pool < one full row
    assert tiny.reserve(0, 100_000)
    assert len(tiny.slot_blocks[0]) == 2


def test_preemption_fires_on_pool_exhaustion_with_free_slots(setup):
    """The page pool is the second admission gate: when free slots exist
    but the pool is hogged by a resident session, the preemption quantum
    must still bound the queue's wait (victim paused, pages recycled)."""
    cfg, model, params = setup
    eng, _ = fresh_engine(setup, max_batch=4, backend="paged",
                          cache_blocks=4, preempt_quantum=2)
    rng = np.random.default_rng(2)
    pa = rng.integers(0, cfg.vocab_size, 30).astype(np.int32)  # 3 pages
    pb = rng.integers(0, cfg.vocab_size, 18).astype(np.int32)  # 2 pages
    eng.submit(Request("hog", pa, max_new_tokens=8))
    eng.submit(Request("small", pb, max_new_tokens=3))
    eng.run()
    assert len(eng.result("hog")) == 8
    assert len(eng.result("small")) == 3
    m = eng.metrics
    assert m.alloc_stalls > 0              # pool (not slots) blocked "small"
    assert m.preemptions > 0               # quantum still bounded its wait
    assert eng.kv.allocator.free_count == 4
    eng.close()


# ------------------------------------------------------------- gauges
def test_occupancy_gauges_track_reservations(setup):
    cfg, model, params = setup
    b = ContiguousBackend(model, max_batch=2, max_seq=128)
    b.reserve(0, 20)
    b.set_length(0, 20)
    occ = b.occupancy()
    assert occ.reserved_tokens == 128              # whole slot regardless
    assert occ.live_tokens == 20
    assert occ.free_blocks == 1                    # slots, for contiguous
    assert 0.0 < occ.utilization < 0.2

    p = PagedBackend(model, max_batch=2, max_seq=128, block_size=16)
    p.reserve(0, 20)
    p.set_length(0, 20)
    occ = p.occupancy()
    assert occ.reserved_tokens == 32               # 2 pages, not max_seq
    assert occ.live_tokens == 20
    assert occ.capacity_tokens == 2 * 128
    assert occ.free_blocks == 16 - 2
    assert occ.utilization == pytest.approx(20 / 32)
    assert occ.fragmentation == pytest.approx(1 - 20 / 32)
