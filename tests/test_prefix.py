"""Cross-session prefix sharing (DESIGN.md §12): refcounted CoW pages,
the token-hash prefix index, content-addressed host chunk sharing, and
session forking — greedy outputs must stay byte-identical to runs
without sharing, and no page may leak or be freed while referenced."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.config.arch import reduced_for_smoke
from repro.config.hardware import PAPER_A100
from repro.configs import get_arch
from repro.core.hcache import HCacheManager
from repro.models import Model
from repro.serving import InferenceEngine, Request
from repro.serving.kv_cache import BlockAllocator, PagedBackend
from repro.serving.prefix_index import PrefixIndex
from repro.serving.request import Phase
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
    mgr = HCacheManager(model, store, hw=PAPER_A100,
                        schedule_override="hidden", store_dtype=np.float32)
    defaults = dict(max_batch=2, max_seq=128, prefill_chunk=8)
    defaults.update(kw)
    return InferenceEngine(model, params, mgr, **defaults), mgr


# ------------------------------------------------- allocator refcounts
def test_block_allocator_double_free_raises():
    """Regression: freeing an already-free page used to append it to the
    LIFO free list a second time, letting two sessions be granted the
    same physical page. It must raise instead."""
    a = BlockAllocator(4)
    got = a.alloc(2)
    a.free(got)
    with pytest.raises(RuntimeError, match="double free"):
        a.free(got)
    # and the free list stayed sane: 4 distinct pages, no duplicates
    assert a.free_count == 4
    assert sorted(a.alloc(4)) == [0, 1, 2, 3]


def test_block_allocator_refcounts():
    a = BlockAllocator(2)
    (b,) = a.alloc(1)
    a.incref(b)
    assert a.refcount(b) == 2
    a.free([b])                        # one holder left: page stays out
    assert a.refcount(b) == 1 and a.free_count == 1
    a.free([b])                        # last holder: back on the free list
    assert a.refcount(b) == 0 and a.free_count == 2
    with pytest.raises(RuntimeError, match="incref of unallocated"):
        a.incref(b)


# -------------------------------------------- backend CoW + index unit
def _write_tokens(backend, slot, toks, start):
    """Write each position's token id as its KV value — content checks
    then reduce to comparing gathers against the slot's token array."""
    n = len(toks) - start
    if n <= 0:
        return
    L = backend.cache["k_pool"].shape[0]
    Kv, hd = backend.cache["k_pool"].shape[-2:]
    vals = jnp.broadcast_to(
        jnp.asarray(toks[start:], jnp.float32)[None, None, :, None, None],
        (L, 1, n, Kv, hd))
    backend.view(slot).write_kv(vals, vals, start)


def _slot_content(backend, slot, n):
    k, _ = backend.view(slot).gather_hist(n)
    return np.asarray(k)[0, 0, :, 0, 0]


def test_cow_divergence_preserves_sibling_content(setup):
    """Two slots share a 2-page prefix; slot 1 diverges inside page 0.
    Only that page is copied (one CoW), and slot 0 still reads the
    original bytes."""
    cfg, model, params = setup
    b = PagedBackend(model, max_batch=2, max_seq=64, block_size=16,
                     num_blocks=8)
    idx = PrefixIndex(b)
    b.prefix_index = idx
    toks = np.arange(100, 140)                      # 40 tokens, 2 full pages
    assert b.reserve(0, 40)
    b.set_length(0, 40)
    _write_tokens(b, 0, toks, 0)
    idx.publish(toks, 40, b.slot_blocks[0])
    assert len(idx) == 2

    blocks, m, _ = idx.match(toks)
    assert m == 32
    b.adopt_shared(1, blocks)
    assert b.reserve(1, 40)
    b.set_length(1, 40)
    assert b.slot_blocks[1][:2] == b.slot_blocks[0][:2]   # truly shared
    _write_tokens(b, 1, toks, 32)                   # private tail, no CoW
    assert b.cow_copies == 0

    fork = toks.copy()
    fork[5] = 999
    # diverge slot 1 at position 5 (inside shared page 0)
    vals = jnp.full((b.cache["k_pool"].shape[0], 1, 1,
                     *b.cache["k_pool"].shape[-2:]), 999.0)
    b.view(1).write_kv(vals, vals, 5)
    assert b.cow_copies == 1
    assert b.slot_blocks[1][0] != b.slot_blocks[0][0]     # page privatized
    assert b.slot_blocks[1][1] == b.slot_blocks[0][1]     # page 1 shared
    np.testing.assert_array_equal(_slot_content(b, 0, 40), toks)
    np.testing.assert_array_equal(_slot_content(b, 1, 40), fork)

    b.free_slot(0)
    b.free_slot(1)
    assert idx.clear() == 2
    assert b.allocator.free_count == 8              # nothing leaked


def test_prefix_index_rejects_divergent_tokens(setup):
    cfg, model, params = setup
    b = PagedBackend(model, max_batch=2, max_seq=64, block_size=16,
                     num_blocks=8)
    idx = PrefixIndex(b)
    toks = np.arange(32)
    b.reserve(0, 32)
    idx.publish(toks, 32, b.slot_blocks[0])
    other = toks.copy()
    other[20] = 7                                   # differs in page 1
    _, m, _ = idx.match(other)
    assert m == 16                                  # page 0 only
    _, m0, _ = idx.match(other, limit=15)
    assert m0 == 0                                  # no full page allowed
    b.free_slot(0)
    idx.clear()
    assert b.allocator.free_count == 8


def test_index_pages_spill_under_pool_pressure(setup):
    """Index-held pages are a cache, not a reservation: when the pool
    cannot satisfy a reservation, LRU index entries are released."""
    cfg, model, params = setup
    b = PagedBackend(model, max_batch=2, max_seq=128, block_size=16,
                     num_blocks=4)
    idx = PrefixIndex(b)
    b.prefix_index = idx
    toks = np.arange(32)
    b.reserve(0, 32)
    idx.publish(toks, 32, b.slot_blocks[0])
    b.free_slot(0)                      # only the index holds the 2 pages
    assert b.allocator.free_count == 2
    assert idx.releasable() == 2
    assert b.can_reserve(64)            # 2 free + 2 releasable
    assert b.reserve(1, 64)             # forces the spill
    assert len(idx) == 0
    b.free_slot(1)
    assert b.allocator.free_count == 4


# ------------------------------------------- hypothesis: invariants
def _check_invariants(b, idx, live_toks):
    # refcount of every page == exactly the number of holders mapping it
    holds = [0] * b.num_blocks
    for blks in b.slot_blocks:
        for blk in blks:
            holds[blk] += 1
    for e in idx._entries.values():
        holds[e.block] += 1
    free = set(b.allocator._free)
    assert len(free) == len(b.allocator._free), "duplicate free-list entry"
    for blk in range(b.num_blocks):
        assert b.allocator.refcount(blk) == holds[blk]
        assert (blk in free) == (holds[blk] == 0)
    # every occupied slot still reads exactly its own token stream
    for slot, toks in live_toks.items():
        np.testing.assert_array_equal(
            _slot_content(b, slot, len(toks)), toks)


@settings(max_examples=8, deadline=None)
@given(ops=st.lists(st.integers(0, 4), min_size=4, max_size=20),
       seed=st.integers(0, 2**31 - 1))
def test_refcount_invariants_random_interleavings(setup, ops, seed):
    """Random admit/publish/diverge/retire/release interleavings: no
    page leaks, no page freed while referenced, every slot's content
    byte-identical to what an unshared run would hold."""
    cfg, model, params = setup
    rng = np.random.default_rng(seed)
    b = PagedBackend(model, max_batch=3, max_seq=64, block_size=16,
                     num_blocks=10)
    idx = PrefixIndex(b)
    b.prefix_index = idx
    shared = [rng.integers(0, 1000, 48), rng.integers(0, 1000, 48)]
    live = {}                                     # slot -> token array
    for op in ops:
        if op == 0:                               # admit (maybe via match)
            free = [s for s in range(3) if s not in live]
            if not free:
                continue
            slot = free[0]
            toks = np.concatenate([shared[int(rng.integers(0, 2))],
                                   rng.integers(0, 1000,
                                                int(rng.integers(0, 16)))])
            blocks, m, _ = idx.match(toks)
            if m:
                b.adopt_shared(slot, blocks)
            if not b.reserve(slot, len(toks)):
                b.free_slot(slot)
                continue
            b.set_length(slot, len(toks))
            _write_tokens(b, slot, toks, m)
            live[slot] = toks
        elif op == 1:                             # publish
            if live:
                slot = int(rng.choice(list(live)))
                idx.publish(live[slot], len(live[slot]),
                            b.slot_blocks[slot])
        elif op == 2:                             # diverge one position
            if live:
                slot = int(rng.choice(list(live)))
                pos = int(rng.integers(0, len(live[slot])))
                tok = int(rng.integers(1000, 2000))
                live[slot] = live[slot].copy()
                live[slot][pos] = tok
                _write_tokens(b, slot, live[slot][:pos + 1], pos)
        elif op == 3:                             # retire
            if live:
                slot = int(rng.choice(list(live)))
                b.free_slot(slot)
                del live[slot]
        else:                                     # index pressure release
            idx.release(1)
        _check_invariants(b, idx, live)
    for slot in list(live):
        b.free_slot(slot)
    idx.clear()
    assert b.allocator.free_count == b.num_blocks     # no page leaked


# ------------------------------------------------- host chunk sharing
def _store():
    return ChunkStore(make_array("dram", 2), chunk_tokens=8)


def test_share_session_dedups_and_diverges():
    s = _store()
    data = np.arange(64, dtype=np.float32).reshape(16, 4)
    s.append_tokens("a", "h", 0, 0, data)
    s.flush("a")
    base = s.bytes_for("a")
    n = s.share_session("a", "b")
    assert n == 2                              # two chunks aliased
    # dedup-aware accounting: the alias costs nothing, dedup_bytes
    # reports what a copy would have cost
    assert s.bytes_for("b") == 0
    assert s.dedup_bytes == base
    np.testing.assert_array_equal(s.read_layer("b", "h", 0, 16), data)
    # fork writer diverges: b overwrites its chunk 0, a keeps the bytes
    s.append_tokens("b", "h", 0, 8, data[:8] + 100)
    s.flush("b")
    np.testing.assert_array_equal(s.read_layer("a", "h", 0, 16), data)
    got_b = s.read_layer("b", "h", 0, 16)
    np.testing.assert_array_equal(got_b[:8], data[:8])
    np.testing.assert_array_equal(got_b[8:], data[:8] + 100)
    assert s.bytes_for("b") > 0                # divergent chunk is real now


def test_owner_extension_shadows_shared_chunk():
    """The owner extending a partial chunk that a fork still references
    rewrites that chunk's key in place — the fork must keep reading the
    old bytes (shadow-out, deferred delete)."""
    s = _store()
    head = np.ones((4, 4), np.float32)
    tail = np.full((4, 4), 2.0, np.float32)
    s.append_tokens("a", "h", 0, 0, head)
    s.flush("a")                               # partial chunk 0: 4 rows
    s.share_session("a", "b")
    s.append_tokens("a", "h", 0, 4, tail)      # extends chunk 0 in place
    s.flush("a")
    np.testing.assert_array_equal(s.read_layer("a", "h", 0, 8),
                                  np.concatenate([head, tail]))
    np.testing.assert_array_equal(s.read_layer("b", "h", 0, 4), head)
    # dropping the last referent frees the shadowed bytes
    used = s.bytes_used
    s.drop_session("b")
    assert s.bytes_used < used


def test_shared_chunks_survive_owner_eviction_and_skip_demotion():
    s = ChunkStore(make_array("dram", 2), chunk_tokens=8,
                   cold_devices=make_array("dram", 2))
    data = np.arange(32, dtype=np.float32).reshape(8, 4)
    s.append_tokens("a", "h", 0, 0, data)
    s.flush("a")
    s.share_session("a", "b")
    # deferred demotion: a shared chunk stays hot until the last referent
    # releases it — a sibling may be restoring from these bytes right now
    assert s.demote_session_to_cold("a") == 0
    assert s.bytes_used > 0 and s.bytes_cold == 0
    # deferred eviction: dropping the owner keeps the shared bytes
    s.drop_session("a")
    np.testing.assert_array_equal(s.read_layer("b", "h", 0, 8), data)
    s.drop_session("b")
    assert s.bytes_used == 0 and s.bytes_cold == 0


def test_pin_chunks_keep_bytes_for_new_aliases():
    s = _store()
    data = np.full((8, 4), 3.0, np.float32)
    s.append_tokens("a", "h", 0, 0, data)
    s.flush("a")
    pins = s.pin_chunks("a", "h", 0, [0])
    s.drop_session("a")
    s.alias_chunk("c", "h", 0, 0, pins[0])     # admission via prefix hit
    s.unpin(pins)
    np.testing.assert_array_equal(s.read_layer("c", "h", 0, 8), data)
    s.drop_session("c")
    assert all(d.bytes_used == 0 for d in s.devices)


# -------------------------------------------------- engine end-to-end
def test_prefix_sharing_outputs_byte_identical(setup):
    """4 sessions over one 48-token system prompt, 2 slots: sharing on
    must produce byte-identical greedy outputs while later sessions skip
    the shared prefill via adopted pages + aliased host chunks."""
    cfg, model, params = setup
    rng = np.random.default_rng(11)
    sys_p = rng.integers(0, cfg.vocab_size, 48).astype(np.int32)
    prompts = [np.concatenate([sys_p, rng.integers(
        0, cfg.vocab_size, 6).astype(np.int32)]) for _ in range(4)]
    results, mets = {}, {}
    for sharing in (False, True):
        eng, _ = fresh_engine(setup, backend="paged",
                              prefix_sharing=sharing)
        for i, p in enumerate(prompts):
            eng.submit(Request(f"p{i}", p, max_new_tokens=4))
        eng.run()
        results[sharing] = {i: eng.result(f"p{i}") for i in range(4)}
        mets[sharing] = eng.metrics
        eng.close()
    assert results[True] == results[False]
    m = mets[True]
    assert m.prefix_hits >= 2                  # late sessions hit
    assert m.restore_skipped_tokens >= 2 * 48  # prefill skipped wholesale
    assert m.dedup_host_bytes > 0              # host streams aliased
    assert mets[False].prefix_hits == 0


@pytest.mark.parametrize("backend", ["contiguous", "paged"])
def test_fork_diverge_evict_restore_roundtrip(setup, backend):
    """fork -> diverge -> evict -> restore on both backends: the fork
    continues from the fork point, both lineages stay independent, and
    everything is byte-identical to the sharing-off (copying) run."""
    cfg, model, params = setup
    rng = np.random.default_rng(5)
    p = rng.integers(0, cfg.vocab_size, 20).astype(np.int32)
    t_fork = int(rng.integers(0, cfg.vocab_size))
    t_src = int(rng.integers(0, cfg.vocab_size))
    results = {}
    for sharing in (False, True):
        eng, _ = fresh_engine(setup, backend=backend,
                              prefix_sharing=sharing)
        eng.submit(Request("src", p, max_new_tokens=6))
        for _ in range(200):
            s = eng.sessions.get("src")
            if (s is not None and s.phase == Phase.DECODE
                    and len(s.generated) >= 3):
                break
            eng.step()
        man = eng.fork_session("src", "fk")
        assert int(man["n_tokens"]) == eng.sessions["src"].total_len - 1
        eng.run()                                  # src retires
        # the fork diverges; src resumes — an evict/restore round trip
        eng.submit(Request("fk", np.asarray([t_fork], np.int32),
                           max_new_tokens=3))
        eng.submit(Request("src", np.asarray([t_src], np.int32),
                           max_new_tokens=3))
        eng.run()
        results[sharing] = (eng.result("src"), eng.result("fk"),
                            eng.metrics.forks)
        if sharing and backend == "paged":
            assert eng.metrics.restore_skipped_tokens > 0
            assert eng.kv.allocator.free_count + len(
                eng.prefix_index._entries) >= 0
        eng.close()
    assert results[True] == results[False]


def test_restore_skip_resumes_round2_identically(setup):
    """Round-2 restoration of a retired session starts at the divergence
    token when its own published pages still sit in the index."""
    cfg, model, params = setup
    rng = np.random.default_rng(9)
    p1 = rng.integers(0, cfg.vocab_size, 40).astype(np.int32)
    p2 = rng.integers(0, cfg.vocab_size, 5).astype(np.int32)
    results = {}
    for sharing in (False, True):
        eng, _ = fresh_engine(setup, backend="paged",
                              prefix_sharing=sharing)
        eng.submit(Request("s", p1, max_new_tokens=4))
        eng.run()
        g1 = eng.result("s")
        eng.submit(Request("s", p2, max_new_tokens=4))
        eng.run()
        results[sharing] = (g1, eng.result("s"))
        if sharing:
            # 43 saved tokens -> 2 full pages adopted, restore starts at 32
            assert eng.metrics.restore_skipped_tokens >= 32
            assert eng.metrics.restored_tokens < 43
        eng.close()
    assert results[True] == results[False]
