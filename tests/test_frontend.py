"""Serving front door (DESIGN.md §14): tokenizer/template stability,
session router steering, engine token-callback seam, pump threading,
OpenAI-compatible API, and the stdlib HTTP binding."""
import asyncio
import json
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config.arch import reduced_for_smoke
from repro.config.hardware import PAPER_A100
from repro.configs import get_arch
from repro.core.hcache import HCacheManager
from repro.frontend import (ByteTokenizer, ChatTemplate, EnginePump,
                            FrontDoor, HttpFrontDoor, Overloaded,
                            RouterBusy, SessionRouter)
from repro.models import Model
from repro.models.module import split
from repro.serving import InferenceEngine, Request
from repro.storage import ChunkStore, make_array


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
                        schedule_override="hidden",
                        store_dtype=np.float32)
    defaults = dict(max_batch=2, max_seq=128, prefill_chunk=8)
    defaults.update(kw)
    return InferenceEngine(model, params, mgr, **defaults)


# ------------------------------------------------------------- tokenizer
def test_tokenizer_roundtrip():
    tok = ByteTokenizer(256)
    ids = [0, 1, 17, 255, 42]
    assert list(tok.encode(tok.decode(ids))) == ids
    # ordinary text maps through UTF-8 bytes mod vocab
    assert list(tok.encode("ab")) == [ord("a"), ord("b")]


def test_chat_template_prefix_stable():
    """The rendered history must be a strict token prefix of the next
    round's render — that is what makes similarity routing exact."""
    tok = ByteTokenizer(256)
    tpl = ChatTemplate(tok)
    msgs = [{"role": "system", "content": "be brief"},
            {"role": "user", "content": "hello"}]
    r1 = tpl.render(msgs)
    reply = tok.decode([5, 9, 250])
    msgs2 = msgs + [{"role": "assistant", "content": reply},
                    {"role": "user", "content": "more"}]
    r2 = tpl.render(msgs2)
    hist = tpl.render(msgs, add_assistant_header=True)
    # round 1 render (prompt + assistant header) prefixes round 2 once
    # the assistant reply continues exactly where generation started
    gen = tok.encode(reply)
    assert np.array_equal(r2[:len(r1)], r1)
    assert np.array_equal(r2[len(r1):len(r1) + len(gen)], gen)
    assert len(r2) > len(hist)


def test_chat_template_token_list_content():
    tpl = ChatTemplate(ByteTokenizer(256))
    a = tpl.render([{"role": "user", "content": [1, 2, 300]}])
    b = tpl.render([{"role": "user", "content": [1, 2, 300 % 256]}])
    assert np.array_equal(a, b)


# ---------------------------------------------------------------- router
def _chain_router(**kw):
    defaults = dict(n_slots=2, block_size=4, reuse_threshold=0.5,
                    max_stored=4)
    defaults.update(kw)
    return SessionRouter(None, **defaults)


def test_router_fresh_then_exact_then_similarity():
    r = _chain_router()
    p1 = np.arange(10, dtype=np.int32)
    d1 = r.route(p1, "conv-a")
    assert d1.kind == "fresh" and len(d1.prompt) == 10
    r.complete(d1, [90, 91, 92])            # history = p1 + [90, 91]
    hist = np.concatenate([p1, [90, 91]]).astype(np.int32)

    p2 = np.concatenate([hist, [92, 7, 8]]).astype(np.int32)
    d2 = r.route(p2, "conv-a")              # same conversation id
    assert d2.kind == "exact"
    assert d2.matched_tokens == len(hist)
    assert list(d2.prompt) == [92, 7, 8]
    r.complete(d2, [93, 94])

    hist2 = np.concatenate([p2, [93]]).astype(np.int32)
    p3 = np.concatenate([hist2, [94, 1]]).astype(np.int32)
    d3 = r.route(p3, None)                  # transcript only, no id
    assert d3.kind == "restore"
    assert d3.matched_tokens == len(hist2)
    assert d3.session_id == d1.session_id
    st = r.stats()
    assert st["exact_hits"] == 1 and st["similarity_hits"] == 1


def test_router_reuse_threshold_rejects_short_match():
    r = _chain_router(reuse_threshold=0.9)
    d1 = r.route(np.arange(8, dtype=np.int32), None)
    r.complete(d1, [50, 51])
    # match covers 9 of 20 tokens < 0.9 -> fresh, not restore
    long = np.concatenate([np.arange(8), [50], np.arange(11)])
    d2 = r.route(long.astype(np.int32), None)
    assert d2.kind == "fresh"


def test_router_busy_conflict_and_cancel():
    r = _chain_router()
    p = np.arange(10, dtype=np.int32)
    d1 = r.route(p, "conv-a")
    with pytest.raises(RouterBusy):
        r.route(np.concatenate([p, [5]]).astype(np.int32), "conv-a")
    r.cancel(d1)                            # failed submit releases it
    r.complete(d1, [1, 2])
    d2 = r.route(np.concatenate([p, [1, 9]]).astype(np.int32), "conv-a")
    assert d2.kind == "exact"


def test_router_displacement_to_stored_registry():
    """Overwritten slots keep their session restorable via the stored
    registry (save-to-store precedes overwrite by construction)."""
    r = _chain_router(n_slots=1)
    p1 = np.arange(8, dtype=np.int32)
    d1 = r.route(p1, "conv-a")
    r.complete(d1, [70, 71])
    hist = np.concatenate([p1, [70]]).astype(np.int32)
    d2 = r.route(np.arange(100, 112, dtype=np.int32), "conv-b")
    assert d2.kind == "fresh"               # displaced conv-a's slot
    r.complete(d2, [1, 2])
    assert r.stats()["overwrites"] >= 1
    assert d1.session_id in r.stored
    # conv-a returns with its transcript: found in the stored registry
    d3 = r.route(np.concatenate([hist, [71, 3]]).astype(np.int32), None)
    assert d3.kind == "restore"
    assert d3.session_id == d1.session_id
    assert d1.session_id not in r.stored    # back in a live slot


def test_router_fork_on_shared_prefix():
    class FakeEngine:
        prefix_sharing = True

        def __init__(self):
            self.forked = []

        def fork_session(self, src, new):
            self.forked.append((src, new))

    eng = FakeEngine()
    r = SessionRouter(eng, n_slots=4, block_size=4)
    p = np.arange(12, dtype=np.int32)
    d1 = r.route(p, "conv-a")
    r.complete(d1, [40, 41])
    hist = np.concatenate([p, [40]]).astype(np.int32)
    # a DIFFERENT conversation continues from conv-a's checkpoint while
    # conv-a still owns the slot -> fork, not steal
    d2 = r.route(np.concatenate([hist, [41, 9]]).astype(np.int32),
                 "conv-b")
    assert d2.kind == "fork"
    assert d2.forked_from == d1.session_id
    assert eng.forked == [(d1.session_id, d2.session_id)]
    assert list(d2.prompt) == [41, 9]
    # with sharing off the same route falls back to a fresh session
    eng.prefix_sharing = False
    d3 = r.route(np.concatenate([hist, [41, 8]]).astype(np.int32),
                 "conv-c")
    assert d3.kind == "fresh"


def test_router_rewritten_history_falls_back():
    r = _chain_router()
    p = np.arange(10, dtype=np.int32)
    d1 = r.route(p, "conv-a")
    r.complete(d1, [5, 6])
    # client edited its transcript: cached state no longer prefixes it
    d2 = r.route(np.arange(50, 64, dtype=np.int32), "conv-a")
    assert d2.kind == "fresh"
    assert d2.session_id != d1.session_id


# --------------------------------------------------- engine callback seam
def test_engine_token_callbacks_exactly_once(setup):
    cfg, _, _ = setup
    engine = fresh_engine(setup)
    tokens, finishes = [], []
    engine.on_token = lambda seq, tok: tokens.append(
        (seq.request.session_id, int(tok)))
    engine.on_finish = lambda seq, reason: finishes.append(
        (seq.request.session_id, reason))
    rng = np.random.default_rng(0)
    engine.submit(Request("a", rng.integers(0, cfg.vocab_size, 12)
                          .astype(np.int32), max_new_tokens=5))
    engine.submit(Request("b", rng.integers(0, cfg.vocab_size, 7)
                          .astype(np.int32), max_new_tokens=3))
    engine.run()
    for sid in ("a", "b"):
        assert [t for s, t in tokens if s == sid] == engine.result(sid)
    assert sorted(finishes) == [("a", "length"), ("b", "length")]
    engine.close()


def test_engine_callbacks_through_pause_resume(setup):
    """Mid-stream eviction: on_pause fires, and the resumed stream emits
    each token exactly once (the resume feed replays the last sampled
    token without re-firing it)."""
    cfg, _, _ = setup
    engine = fresh_engine(setup, max_batch=1, preempt_quantum=2)
    tokens, pauses = [], []
    engine.on_token = lambda seq, tok: tokens.append(
        (seq.request.session_id, int(tok)))
    engine.on_pause = lambda seq: pauses.append(seq.request.session_id)
    rng = np.random.default_rng(1)
    engine.submit(Request("a", rng.integers(0, cfg.vocab_size, 10)
                          .astype(np.int32), max_new_tokens=6))
    engine.submit(Request("b", rng.integers(0, cfg.vocab_size, 10)
                          .astype(np.int32), max_new_tokens=6))
    engine.run()
    assert engine.metrics.preemptions > 0 and pauses
    for sid in ("a", "b"):
        assert [t for s, t in tokens if s == sid] == engine.result(sid)
    engine.close()


def test_engine_callbacks_on_restored_round(setup):
    """Round 2 restores the stored history; only NEW tokens fire."""
    cfg, _, _ = setup
    engine = fresh_engine(setup)
    tokens = []
    engine.on_token = lambda seq, tok: tokens.append(int(tok))
    rng = np.random.default_rng(2)
    engine.submit(Request("a", rng.integers(0, cfg.vocab_size, 14)
                          .astype(np.int32), max_new_tokens=4))
    engine.run()
    r1 = list(tokens)
    assert r1 == engine.result("a")
    tokens.clear()
    engine.submit(Request("a", rng.integers(0, cfg.vocab_size, 6)
                          .astype(np.int32), max_new_tokens=3))
    engine.run()
    assert engine.metrics.restored_tokens > 0
    assert tokens == engine.result("a")     # round-2 tokens only
    engine.close()


def test_recoverable_sessions(setup):
    cfg, _, _ = setup
    engine = fresh_engine(setup)
    rng = np.random.default_rng(3)
    assert engine.recoverable_sessions() == []
    for sid in ("u1", "u2"):
        engine.submit(Request(sid, rng.integers(0, cfg.vocab_size, 9)
                              .astype(np.int32), max_new_tokens=3))
    engine.run()
    assert sorted(engine.recoverable_sessions()) == ["u1", "u2"]
    engine.close()


def test_request_arrival_stamping(setup):
    cfg, _, _ = setup
    engine = fresh_engine(setup)
    rng = np.random.default_rng(4)
    p = rng.integers(0, cfg.vocab_size, 8).astype(np.int32)
    r1 = Request("a", p, max_new_tokens=1)
    engine.submit(r1)
    assert r1.arrival_time > 0.0 and r1.arrival_step >= 0
    # a caller that pre-stamped (the front door at ingress) is respected
    r2 = Request("b", p, max_new_tokens=1, priority=2)
    r2.arrival_time = 123.0
    r2.arrival_step = 7
    engine.submit(r2)
    assert r2.arrival_time == 123.0 and r2.arrival_step == 7
    assert r2.priority == 2
    engine.run()
    engine.close()


def test_metrics_to_dict_json_serializable(setup):
    cfg, _, _ = setup
    engine = fresh_engine(setup)
    rng = np.random.default_rng(5)
    engine.submit(Request("a", rng.integers(0, cfg.vocab_size, 8)
                          .astype(np.int32), max_new_tokens=2))
    engine.run()
    d = engine.metrics.to_dict()
    blob = json.loads(json.dumps(d))
    assert blob["decode_steps"] == engine.metrics.decode_steps
    assert blob["ttft_wall"]["n"] == 1
    engine.close()


# ------------------------------------------------------------------ pump
def test_pump_stream_and_backpressure(setup):
    cfg, _, _ = setup
    engine = fresh_engine(setup)
    pump = EnginePump(engine, max_pending=1)
    rng = np.random.default_rng(6)
    p = rng.integers(0, cfg.vocab_size, 8).astype(np.int32)
    # pump not started: submissions queue deterministically
    sub = pump.submit(Request("a", p, max_new_tokens=3))
    with pytest.raises(Overloaded):
        pump.submit(Request("b", p, max_new_tokens=3))
    pump.start()
    assert sub.wait(60.0)
    assert sub.finish_reason == "length"
    assert sub.tokens == engine.result("a")
    assert len(sub.token_times) == 3 and sub.ttft > 0
    pump.close()
    assert pump.closed
    pump.close()                            # idempotent


def test_pump_call_runs_on_pump_thread(setup):
    engine = fresh_engine(setup)
    pump = EnginePump(engine)
    # not started -> executes inline
    assert pump.call(lambda: threading.current_thread().name).result() \
        == threading.current_thread().name
    pump.start()
    name = pump.call(lambda: threading.current_thread().name).result(30.0)
    assert name == "engine-pump"
    pump.close()


# ------------------------------------------------------------------- api
def _mk_api(setup, **pump_kw):
    engine = fresh_engine(setup)
    pump = EnginePump(engine, **pump_kw).start()
    api = FrontDoor(pump, SessionRouter(engine, block_size=16))
    return engine, pump, api


def test_api_chat_rounds_restore_and_match_reference(setup):
    """Round 2 via conversation_id (exact), round 3 via transcript only
    (similarity); outputs byte-identical to a one-shot full-history
    completion on a fresh session."""
    engine, pump, api = _mk_api(setup)

    async def main():
        msgs = [{"role": "system", "content": "sys"},
                {"role": "user", "content": "hello"}]
        st, r1 = await api.handle("POST", "/v1/chat/completions",
                                  {"messages": msgs, "max_tokens": 4})
        assert st == 200 and r1["hcache"]["route"] == "fresh"
        conv = r1["conversation_id"]
        c1 = r1["choices"][0]["message"]["content"]
        assert r1["choices"][0]["finish_reason"] == "length"

        msgs2 = msgs + [{"role": "assistant", "content": c1},
                        {"role": "user", "content": "again"}]
        st, r2 = await api.handle("POST", "/v1/chat/completions",
                                  {"messages": msgs2, "max_tokens": 4,
                                   "conversation_id": conv})
        assert st == 200 and r2["hcache"]["route"] == "exact"
        assert engine.metrics.restored_tokens > 0
        c2 = r2["choices"][0]["message"]["content"]

        msgs3 = msgs2 + [{"role": "assistant", "content": c2},
                         {"role": "user", "content": "more"}]
        st, r3 = await api.handle("POST", "/v1/chat/completions",
                                  {"messages": msgs3, "max_tokens": 4})
        assert st == 200 and r3["hcache"]["route"] == "restore"
        assert r3["hcache"]["matched_tokens"] > 0

        full = api.template.render(msgs3)
        st, ref = await api.handle("POST", "/v1/completions",
                                   {"prompt": [int(t) for t in full],
                                    "max_tokens": 4})
        assert st == 200 and ref["hcache"]["route"] == "fresh"
        got = list(api.tokenizer.encode(
            r3["choices"][0]["message"]["content"]))
        assert got == ref["choices"][0]["tokens"]
        assert api.router.hit_rate > 0

    asyncio.run(main())
    pump.close()


def test_api_streaming_delivers_incrementally(setup):
    engine, pump, api = _mk_api(setup)

    async def main():
        st, agen = await api.handle(
            "POST", "/v1/chat/completions",
            {"messages": [{"role": "user", "content": "stream me"}],
             "max_tokens": 6, "stream": True})
        assert st == 200
        # park the pump thread between steps: generation provably can't
        # complete until we release it, so receiving the first chunk now
        # proves streaming delivery, not post-hoc buffering
        gate = threading.Event()
        pump.call(gate.wait)
        it = agen.__aiter__()
        seen = [(time.perf_counter(), await it.__anext__())]
        assert pump.pending() > 0           # still mid-generation
        gate.set()
        async for chunk in it:
            seen.append((time.perf_counter(), chunk))
        assert seen[-1][1] == "data: [DONE]\n\n"
        bodies = [json.loads(c[len("data: "):])
                  for _, c in seen[:-1]]
        contents = [b["choices"][0]["delta"].get("content")
                    for b in bodies if "delta" in b["choices"][0]]
        assert sum(1 for c in contents if c) == 6   # one chunk per token
        assert bodies[-1]["choices"][0]["finish_reason"] == "length"
        assert bodies[-1]["hcache"]["route"] == "fresh"
        assert seen[0][0] < seen[-1][0]

    asyncio.run(main())
    pump.close()


def test_api_backpressure_and_busy_statuses(setup):
    engine = fresh_engine(setup)
    pump = EnginePump(engine, max_pending=1)    # NOT started: no progress
    api = FrontDoor(pump, SessionRouter(engine, block_size=16))

    async def main():
        st, _ = await api.handle(
            "POST", "/v1/chat/completions",
            {"messages": [{"role": "user", "content": "one"}],
             "max_tokens": 2, "stream": True,
             "conversation_id": "conv-x"})
        assert st == 200
        # same conversation again while in flight -> 409
        st, err = await api.handle(
            "POST", "/v1/chat/completions",
            {"messages": [{"role": "user", "content": "one two"}],
             "max_tokens": 2, "conversation_id": "conv-x"})
        assert st == 409 and err["error"]["type"] == "conversation_busy"
        # different conversation -> queue-depth cap -> 429, and the
        # router slot it grabbed is released for a retry
        st, err = await api.handle(
            "POST", "/v1/chat/completions",
            {"messages": [{"role": "user", "content": "other"}],
             "max_tokens": 2, "conversation_id": "conv-y"})
        assert st == 429 and err["error"]["type"] == "overloaded"
        assert not any(s.busy and s.conversation_id == "conv-y"
                       for s in api.router.slots)
        st, _ = await api.handle("GET", "/healthz", None)
        assert st == 200

    asyncio.run(main())
    pump.close(force=True)


def test_api_validation_and_metrics_endpoint(setup):
    engine, pump, api = _mk_api(setup)

    async def main():
        st, err = await api.handle("POST", "/v1/chat/completions",
                                   {"messages": []})
        assert st == 400
        st, err = await api.handle("POST", "/v1/completions", {})
        assert st == 400
        st, _ = await api.handle("GET", "/nope", None)
        assert st == 404
        st, models = await api.handle("GET", "/v1/models", None)
        assert st == 200 and models["data"][0]["id"] == api.model_name
        st, m = await api.handle("GET", "/metrics", None)
        assert st == 200
        json.dumps(m)                       # whole document serializes
        assert "engine" in m and "router" in m and "pump" in m
        for k in ("queue_wait_sum", "admitted", "prefill_wall_sum",
                  "prefill_tokens", "prefill_traces", "save_wall_sum",
                  "saved_tokens", "save_d2h_bytes", "restore_read_wall",
                  "restore_upload_wall", "restore_write_wall",
                  "restore_h2d_bytes", "restore_hidden_layer_tokens",
                  "restore_kv_layer_tokens",
                  "restore_recompute_layer_tokens",
                  "restore_hidden_h2d_bytes", "decode_kv_bytes"):
            assert isinstance(m["engine"][k], (int, float)), k

    asyncio.run(main())
    pump.close()


# ------------------------------------------------------------------ http
def test_http_binding_smoke(setup):
    """Satellite (f): ephemeral-port HTTP server, one streaming + one
    non-streaming request over real sockets, clean shutdown with
    ``engine.close()`` reached and no leaked threads."""
    before = set(threading.enumerate())
    engine = fresh_engine(setup)
    pump = EnginePump(engine).start()
    api = FrontDoor(pump)

    async def request(port, body, stream):
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        doc = json.dumps(body).encode()
        writer.write(
            b"POST /v1/chat/completions HTTP/1.1\r\n"
            b"Host: localhost\r\nContent-Type: application/json\r\n"
            + f"Content-Length: {len(doc)}\r\n\r\n".encode() + doc)
        await writer.drain()
        status = (await reader.readline()).decode()
        while (await reader.readline()).strip():
            pass                            # headers
        raw = (await reader.read()).decode()   # Connection: close -> EOF
        writer.close()
        await writer.wait_closed()
        return status, raw

    async def main():
        srv = await HttpFrontDoor(api, port=0).start()
        assert srv.port != 0
        st, raw = await request(
            srv.port, {"messages": [{"role": "user", "content": "hi"}],
                       "max_tokens": 3}, stream=False)
        assert "200" in st
        doc = json.loads(raw)
        assert len(doc["choices"][0]["message"]["content"]) == 3
        st, raw = await request(
            srv.port, {"messages": [{"role": "user", "content": "hi2"}],
                       "max_tokens": 3, "stream": True}, stream=True)
        assert "200" in st
        events = [e for e in raw.split("\n\n") if e.startswith("data: ")]
        assert events[-1] == "data: [DONE]"
        deltas = [json.loads(e[len("data: "):]) for e in events[:-1]]
        assert sum(1 for d in deltas
                   if d["choices"][0]["delta"].get("content")) == 3
        await srv.close()

    asyncio.run(main())
    pump.close()
    assert pump.closed
    # engine.close() was reached: the saver's daemon threads are joined
    assert not any(t.is_alive() for t in engine.mgr.saver._threads)
    leaked = [t for t in threading.enumerate()
              if t not in before and t.is_alive()]
    assert not leaked, leaked
