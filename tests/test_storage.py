"""Chunk store + two-stage saver: roundtrips, striping, resume, hypothesis."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.storage import (ChunkStore, DirectSaver, SimulatedSSD,
                           SnapshotTask, TwoStageSaver, make_array)


def make_store(n_dev=4, chunk=16, kind="dram"):
    return ChunkStore(make_array(kind, n_dev), chunk_tokens=chunk)


def test_roundtrip_layer_before_token_to_token_before_layer():
    """The core layout mismatch (§4.2): save layer-by-layer in token
    increments, read back whole layers."""
    store = make_store()
    data = {li: np.arange(40 * 8, dtype=np.float32).reshape(40, 8) + li
            for li in range(3)}
    for step in range(0, 40, 5):             # autoregressive growth
        for li in range(3):
            store.append_tokens("s", "h", li, step, data[li][step:step + 5])
    store.flush("s")
    for li in range(3):
        got = store.read_layer("s", "h", li, 40)
        np.testing.assert_array_equal(got, data[li])


def test_chunks_striped_round_robin():
    store = make_store(n_dev=4, chunk=8)
    store.append_tokens("s", "h", 0, 0, np.ones((64, 4), np.float16))
    store.flush("s")
    used = [d.bytes_used for d in store.devices]
    assert all(b > 0 for b in used), used     # all devices hold chunks


def test_resume_mid_chunk():
    """Multi-round sessions append at arbitrary offsets; previously-flushed
    partial chunks must be recovered, not zero-padded."""
    store = make_store(chunk=16)
    a = np.arange(10 * 4, dtype=np.float32).reshape(10, 4)
    b = np.arange(10 * 4, 22 * 4, dtype=np.float32).reshape(12, 4)
    store.append_tokens("s", "h", 0, 0, a)
    store.flush("s")
    store.append_tokens("s", "h", 0, 10, b)   # resumes inside chunk 0
    store.flush("s")
    got = store.read_layer("s", "h", 0, 22)
    np.testing.assert_array_equal(got, np.concatenate([a, b]))


def test_manifest_and_recovery_listing():
    store = make_store()
    store.put_manifest("alice", {"n_tokens": 7, "methods": ["hidden"]})
    store.put_manifest("bob", {"n_tokens": 3, "methods": ["kv"]})
    assert store.sessions() == ["alice", "bob"]
    assert store.get_manifest("alice")["n_tokens"] == 7
    store.drop_session("alice")
    assert store.sessions() == ["bob"]
    assert store.get_manifest("alice") is None


def test_file_backend_survives_reopen(tmp_path):
    store = ChunkStore(make_array("file", 2, root=str(tmp_path)),
                       chunk_tokens=8)
    store.append_tokens("s", "h", 0, 0, np.ones((8, 2), np.float16))
    store.put_manifest("s", {"n_tokens": 8, "methods": []})
    store2 = ChunkStore(make_array("file", 2, root=str(tmp_path)),
                        chunk_tokens=8)
    assert store2.sessions() == ["s"]
    np.testing.assert_array_equal(store2.read_layer("s", "h", 0, 8),
                                  np.ones((8, 2), np.float16))


@settings(max_examples=25, deadline=None)
@given(
    chunk=st.sampled_from([4, 16, 64]),
    pieces=st.lists(st.integers(1, 30), min_size=1, max_size=12),
    width=st.integers(1, 8),
)
def test_append_roundtrip_property(chunk, pieces, width):
    """Any partition of a token stream into appends reads back intact."""
    store = make_store(chunk=chunk)
    total = sum(pieces)
    data = np.random.default_rng(0).normal(
        size=(total, width)).astype(np.float32)
    off = 0
    for n in pieces:
        store.append_tokens("s", "h", 0, off, data[off:off + n])
        off += n
    store.flush("s")
    np.testing.assert_array_equal(store.read_layer("s", "h", 0, total), data)


def test_session_id_with_slash():
    """Session ids containing '/' (tenant/user) must not collide with the
    key separator: listing, reading and dropping all work."""
    store = make_store(chunk=8)
    sid = "tenant/alice/chat-1"
    store.append_tokens(sid, "h", 0, 0, np.ones((8, 2), np.float32))
    store.flush(sid)
    store.put_manifest(sid, {"n_tokens": 8, "methods": ["hidden"]})
    store.put_manifest("bob", {"n_tokens": 1, "methods": []})
    assert store.sessions() == ["bob", sid]
    np.testing.assert_array_equal(store.read_layer(sid, "h", 0, 8),
                                  np.ones((8, 2), np.float32))
    store.drop_session(sid)
    assert store.sessions() == ["bob"]
    assert store.get_manifest(sid) is None


def test_file_backend_session_id_with_double_underscore(tmp_path):
    """Regression: the old filename scheme mapped '/' -> '__' and keys()
    mapped '__' -> '/', mangling session ids that legitimately contain
    '__'. The percent-encoding is injective: list/read/drop round-trip."""
    store = ChunkStore(make_array("file", 2, root=str(tmp_path)),
                       chunk_tokens=8)
    sid = "tenant__alice__chat%1"
    store.append_tokens(sid, "h", 0, 0, np.ones((8, 2), np.float32))
    store.flush(sid)
    store.put_manifest(sid, {"n_tokens": 8, "methods": ["hidden"]})
    store2 = ChunkStore(make_array("file", 2, root=str(tmp_path)),
                        chunk_tokens=8)
    assert store2.sessions() == [sid]
    np.testing.assert_array_equal(store2.read_layer(sid, "h", 0, 8),
                                  np.ones((8, 2), np.float32))
    store2.drop_session(sid)
    assert store2.sessions() == []
    assert store2.get_manifest(sid) is None


def test_two_stage_saver_reraises_daemon_exception():
    """A stage-2 write failure must not be lost in the daemon thread:
    drain() re-raises the first captured exception (and the daemon
    thread survives to process later tasks)."""
    store = make_store()
    saver = TwoStageSaver(store, n_threads=1)
    bad = SnapshotTask(["s", "t"], "h", 0, [0],   # missing start for "t"
                       np.ones((2, 8, 4), np.float16))
    saver.snapshot(bad)                         # daemon IndexErrors on b=1
    with pytest.raises(IndexError):
        saver.drain()
    saver.snapshot(SnapshotTask(["s"], "h", 0, [0],
                                np.ones((1, 8, 4), np.float16)))
    saver.drain()                               # exception was cleared
    saver.close()


def test_chunk_store_cold_tier_demotion():
    """demote_session_to_cold moves a session's bytes out of the hot
    (budgeted) tier; reads fall back transparently, drops cover both."""
    cold = make_array("dram", 4)
    store = ChunkStore(make_array("dram", 4), chunk_tokens=8,
                       cold_devices=cold)
    data = np.arange(24 * 4, dtype=np.float32).reshape(24, 4)
    store.append_tokens("s", "h", 0, 0, data)
    store.flush("s")
    store.put_manifest("s", {"n_tokens": 24, "methods": ["hidden"]})
    hot_before = store.bytes_used
    moved = store.demote_session_to_cold("s")
    assert moved == hot_before > 0
    assert store.bytes_used == 0 and store.bytes_cold == moved
    np.testing.assert_array_equal(store.read_layer("s", "h", 0, 24), data)
    assert store.get_manifest("s")["n_tokens"] == 24
    assert store.sessions() == ["s"]
    assert store.demote_session_to_cold("s") == 0     # nothing hot left
    store.drop_session("s")
    assert store.sessions() == [] and store.bytes_cold == 0


def test_bytes_for_per_session_per_stream():
    store = make_store(chunk=8)
    store.append_tokens("a", "h", 0, 0, np.ones((8, 4), np.float32))
    store.append_tokens("a", "kvk", 0, 0, np.ones((8, 2), np.float32))
    store.append_tokens("b", "h", 0, 0, np.ones((8, 4), np.float32))
    store.flush("a")
    store.flush("b")
    assert store.bytes_for("a", "h") == 8 * 4 * 4
    assert store.bytes_for("a", "kvk") == 8 * 2 * 4
    assert store.bytes_for("a") == 8 * 6 * 4
    assert store.bytes_for("b") == 8 * 4 * 4


def test_layer_available_checks_covering_chunks():
    """layer_available must check the chunks covering the queried range,
    not only chunk 0 (a crash mid-save leaves a prefix of chunks)."""
    store = make_store(chunk=8)
    store.append_tokens("s", "h", 0, 0, np.ones((12, 2), np.float32))
    store.flush("s")                       # chunks 0 (full) + 1 (partial)
    assert store.layer_available("s", "h", 0)
    assert store.layer_available("s", "h", 0, n_tokens=12)
    # range ends inside the flushed short chunk: NOT available
    assert not store.layer_available("s", "h", 0, n_tokens=16)
    assert not store.layer_available("s", "h", 0, n_tokens=20)
    assert not store.layer_available("s", "h", 1)
    # unflushed partial covering the range counts too
    store.append_tokens("s", "h", 1, 0, np.ones((5, 2), np.float32))
    assert store.layer_available("s", "h", 1, n_tokens=5)


def test_read_layer_async_completions():
    """The batched async read reports per-device completion times that
    aggregate striped bandwidth."""
    store = make_store(n_dev=4, chunk=16, kind="ssd")
    store.append_tokens("s", "h", 0, 0, np.ones((64, 32), np.float16))
    store.flush("s")
    store.sync_clocks(0.0)
    r = store.read_layer_async("s", "h", 0, 64)
    assert r.data.shape == (64, 32)
    assert len(r.device_completions) == 4
    assert r.completion == max(r.device_completions) > 0


def test_simulated_ssd_bandwidth_aggregation():
    """Reading a layer striped over 4 SSDs completes ~4x faster than on 1."""
    total = 64 * 16

    def read_time(n_dev):
        store = make_store(n_dev=n_dev, chunk=64, kind="ssd")
        store.append_tokens("s", "h", 0, 0,
                            np.ones((total, 256), np.float16))
        store.flush("s")
        store.sync_clocks(0.0)
        store.read_layer("s", "h", 0, total)
        return store.read_completion()

    t1, t4 = read_time(1), read_time(4)
    # same total bytes in both cases => ideal 4x; latency eats a little
    assert t1 / t4 > 2.5


def test_two_stage_saver_offloads_critical_path():
    store = make_store(kind="ssd")
    saver = TwoStageSaver(store, ring_slots=64)
    direct = DirectSaver(make_store(kind="ssd"))

    def task(i):
        return SnapshotTask(["s"], "h", 0, [i * 8],
                            np.ones((1, 8, 64), np.float16))

    ts_cost = sum(saver.snapshot(task(i)) for i in range(20))
    d_cost = sum(direct.snapshot(task(i)) for i in range(20))
    saver.drain()
    assert ts_cost < d_cost       # stage-1 copy < synchronous SSD write
    store.flush("s")
    got = store.read_layer("s", "h", 0, 160)
    assert got.shape == (160, 64)
    saver.close()
