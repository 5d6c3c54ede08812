#!/usr/bin/env python3
"""Bring-up check of the HCache serving path on a TPU.

    python3 chip_smoke.py              # one chip
    python3 chip_smoke.py --chips 4    # tp=4 against tp=1 on four chips

Model: qwen2-7b (configs/qwen2_7b.py) at its published widths — d_model
3584, 28 query / 4 KV heads of 128, d_ff 18944, vocab 152064, QKV bias,
rope theta 1e6 — in bf16 with random weights from ``SEED``. Depth is
cut to 14 of 28 layers: the first stage of a two-stage pipeline
deployment, 4.35 B parameters, 8.71 GB. The init leaves the QKV biases
at zero; the script draws them from the seed so that the restore
kernel's bias path carries values.

Engine: ``launch/serve.py``'s ``build_engine`` with the paged backend and
4,500 pages of 16 tokens (2.06 GB at 28 KB per token). Decode is jitted
without donation, so a step holds a second copy of the pool: weights +
two pools + activations fit in 16 GB.

Traffic, from ``SEED``:

  round 1  four sessions with 2k-4k token prompts, 16 new tokens each,
           one at a time. Each is saved under one restore method —
           hidden, kv, recompute, or the scheduler's own plan — pinned
           with the manager's ``schedule_override`` (the method a layer
           is restored by is the one its state was saved under). The
           first goes through the front door's /v1/chat/completions on
           127.0.0.1, the others through the engine pump.
  evict    a finished round frees the session's pages; the script checks
           that all four live only in the host store.
  round 2  each session gets a follow-up prompt and is restored by its
           method. Its twin, a fresh session that prefills the same
           history and follow-up and is never evicted, gives the
           reference next-token logits.

Check: per restored session, max |Δlogit| against the twin within
``LOGIT_ATOL`` (argued below) and the same greedy token (unless the
twin's own top two are a near-tie, see ``greedy_agrees``); every HTTP
status 200; the restore projection that ran lowers to the Mosaic kernel
(``tpu_custom_call``), not to interpret mode. ``--chips 4`` runs only the
tensor-parallel phase: the same traffic at tp=4 (one KV head per chip)
and at tp=1, outputs compared, per-device bytes reported.

Everything but the last line of stdout is a report. The last line is
``{"ok": true, "device": {...}}``. Without a TPU, or outside a checkout
of the repository, the script fails before printing it.
"""
from __future__ import annotations

import argparse
import asyncio
import dataclasses
import http.client
import json
import sys
import threading
import time
from collections import defaultdict
from pathlib import Path

import numpy as np

LAYERS = 14                 # of 28: the first of two pipeline stages
PAGES = 4500                # 16-token pages: 2.06 GB per pool
MAX_SEQ = 4352              # prompt + history + follow-up + generation
NEW_TOKENS = 16
SEED = 0                    # weights, biases and traffic
METHODS = ("hidden", "kv", "recompute", None)   # None: scheduler's plan
# Both paths keep activations, K/V and the residual stream in bf16 (8
# significant bits). They round at different places — decode vs
# prefill for the round-1 answer, the restore kernel's f32 epilogue vs
# the model's bf16 projection, one full-sequence pass vs 128-token
# chunks for recompute, a different chunking of the follow-up — and
# every layer carries those differences on to the final hidden state.
# Logits here reach |logit| ~ 5, where one bf16 ulp of their inputs is
# 2^-5; the bound is 4 such ulps. A wrong layer, head, position or RoPE
# angle moves logits by O(1).
LOGIT_ATOL = 0.125


def fail(msg: str) -> None:
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def report(msg: str) -> None:
    print(msg, flush=True)


# ----------------------------------------------------------- measurement
class CompileClock:
    """Seconds JAX spent compiling, and persistent-cache hits/misses,
    from JAX's own monitoring events."""

    def __init__(self):
        import jax
        self.secs = defaultdict(float)
        self.counts = defaultdict(int)
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **_):
        self.secs[event] += duration

    def _event(self, event, **_):
        self.counts[event] += 1

    def snapshot(self):
        return (self.secs["/jax/core/compile/backend_compile_duration"],
                self.counts["/jax/compilation_cache/cache_hits"],
                self.counts["/jax/compilation_cache/cache_misses"])


class Phases:
    def __init__(self, clock: CompileClock):
        self.clock = clock
        self.rows = []

    def run(self, name, fn, *a, **kw):
        c0, h0, m0 = self.clock.snapshot()
        t0 = time.perf_counter()
        out = fn(*a, **kw)
        wall = time.perf_counter() - t0
        c1, h1, m1 = self.clock.snapshot()
        self.rows.append((name, wall, c1 - c0))
        report(f"phase {name}: wall {wall:.3f} s, of which backend "
               f"compile {c1 - c0:.3f} s (persistent cache hits "
               f"{h1 - h0}, misses {m1 - m0})")
        return out


# ---------------------------------------------------------------- model
def smoke_config():
    from repro.configs import get_arch
    return dataclasses.replace(get_arch("qwen2-7b"), n_layers=LAYERS)


def make_engine(cfg, *, seed: int, tp: int, pages: int = PAGES,
                max_seq: int = MAX_SEQ):
    """The engine through serve.py's construction path, then random
    QKV biases from the seed (placed where the parameters live)."""
    import jax
    import jax.numpy as jnp
    from repro.launch.serve import build_engine, make_parser
    args = make_parser().parse_args([
        "--arch", cfg.name, "--full", "--backend", "paged",
        "--max-batch", "4", "--max-seq", str(max_seq),
        "--cache-blocks", str(pages), "--tp", str(tp)])
    engine = build_engine(args, cfg=cfg, seed=seed)
    attn = engine.params["blocks"]["attn"]
    key = jax.random.PRNGKey(seed + 1)
    for name in ("bq", "bk", "bv"):
        if name in attn:
            key, sub = jax.random.split(key)
            b = 0.5 * jax.random.normal(sub, attn[name].shape, jnp.float32)
            attn[name] = engine.tp.replicate(b.astype(attn[name].dtype))
    jax.block_until_ready(engine.params)
    return engine


def record_logits(engine):
    """Final-chunk next-token logits of every prefill, per session id
    (the engine samples from them and keeps nothing)."""
    ad = engine.adapter
    prefill = ad.prefill_chunk
    got = {}

    def recorded(params, seq, chunk, hist, **kw):
        out = prefill(params, seq, chunk, hist, **kw)
        if seq.prefill_done + len(chunk) >= len(seq.effective_prompt):
            got[seq.request.session_id] = np.asarray(
                out["logits"][0, -1], np.float32)
        return out

    ad.prefill_chunk = recorded
    return got


# -------------------------------------------------------------- traffic
def make_traffic(vocab: int, seed: int, *, lo: int = 2048, hi: int = 4096,
                 follow: int = 64):
    rng = np.random.default_rng(seed)
    lens = rng.integers(lo, hi + 1, len(METHODS))
    prompts = [rng.integers(0, vocab, int(n)).astype(np.int32)
               for n in lens]
    follows = [rng.integers(0, vocab, int(n)).astype(np.int32)
               for n in rng.integers(follow // 2, follow + 1, len(METHODS))]
    return prompts, follows


def post_json(port: int, path: str, body: dict):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=3600)
    try:
        conn.request("POST", path, body=json.dumps(body),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        return resp.status, json.loads(resp.read())
    finally:
        conn.close()


class FrontDoorThread:
    """The front door (pump + router + HTTP socket) with its asyncio loop
    on a background thread, so this script can be its client."""

    def __init__(self, engine):
        from repro.frontend import start_front_door
        self.loop = asyncio.new_event_loop()
        self.thread = threading.Thread(target=self.loop.run_forever,
                                       daemon=True)
        self.thread.start()
        self.pump, self.srv = asyncio.run_coroutine_threadsafe(
            start_front_door(engine, "127.0.0.1", 0), self.loop).result()

    def close(self):
        asyncio.run_coroutine_threadsafe(self.srv.close(),
                                         self.loop).result()
        self.loop.call_soon_threadsafe(self.loop.stop)
        self.thread.join()
        self.loop.close()
        self.pump.close()          # quiesces, drains the saver, closes


def _wait(pump, what: str, done, timeout: float = 1800.0):
    """Poll ``done(poll_seconds)`` until it returns True; fail if the
    engine pump dies (an exception in the engine ends its thread and
    would otherwise leave every request hanging)."""
    t0 = time.perf_counter()
    while not done(5.0):
        if not pump.alive:
            fail(f"{what}: the engine pump died (see its traceback above)")
        if time.perf_counter() - t0 > timeout:
            fail(f"{what} did not finish in {timeout:.0f} s")


def _request(pump, request):
    sub = pump.submit(request)
    _wait(pump, request.session_id, sub.wait)
    return list(sub.tokens)


def _post(pump, port: int, path: str, body: dict):
    """``post_json`` on a daemon thread, so that a dead pump fails the
    run instead of leaving it blocked on the socket."""
    got = []
    t = threading.Thread(target=lambda: got.append(
        post_json(port, path, body)), daemon=True)
    t.start()
    _wait(pump, path, lambda s: (t.join(s), not t.is_alive())[1])
    if not got:
        fail(f"POST {path} raised (see the traceback above)")
    return got[0]


def serve_traffic(engine, prompts, follows, phases: Phases, *,
                  twins: bool = True):
    """Round 1, eviction check, round 2 (+ twins) through the front door
    and its pump. Returns one dict per session."""
    from repro.serving import Request
    mgr = engine.mgr
    logits = record_logits(engine)
    fd = FrontDoorThread(engine)
    pump = fd.pump
    out = []
    try:
        def pin(method):
            pump.call(setattr, mgr, "schedule_override", method).result()

        def round1():
            for i, (method, prompt) in enumerate(zip(METHODS, prompts)):
                pin(method)
                if i == 0:
                    status, body = _post(pump, fd.srv.port,
                                         "/v1/chat/completions", {
                        "messages": [{"role": "user",
                                      "content": prompt.tolist()}],
                        "max_tokens": NEW_TOKENS})
                    if status != 200:
                        fail(f"/v1/chat/completions returned {status}: "
                             f"{body}")
                    sid = body["hcache"]["session_id"]
                    n_gen = body["usage"]["completion_tokens"]
                    report(f"round 1 {sid}: HTTP {status}, "
                           f"{body['usage']['prompt_tokens']} prompt + "
                           f"{n_gen} completion tokens")
                    gen = engine.result(sid)
                    if n_gen != NEW_TOKENS or len(gen) != NEW_TOKENS:
                        fail(f"{sid}: {n_gen} tokens, want {NEW_TOKENS}")
                else:
                    sid = f"s{i}"
                    gen = _request(pump, Request(
                        sid, prompt, max_new_tokens=NEW_TOKENS))
                    if len(gen) != NEW_TOKENS:
                        fail(f"{sid}: {len(gen)} tokens")
                out.append({"sid": sid, "pin": method or "plan",
                            "round1": gen})
            pin(None)
        phases.run("round1", round1)

        # every round-1 session has retired: pages free, state on host
        kv = engine.kv
        if any(s is not None for s in engine.slots) or \
                kv.allocator.free_count != kv.num_blocks:
            fail("round-1 sessions still hold device pages")
        for r in out:
            man = mgr.store.get_manifest(r["sid"])
            r["n_hist"] = int(man["n_tokens"])
            r["methods"] = list(man["methods"])
            r["history"] = np.asarray(
                mgr.store.get_blob(r["sid"], "tok", 0))[:r["n_hist"]]
            want = r["pin"]
            if want != "plan" and set(r["methods"]) != {want}:
                fail(f"{r['sid']} saved as {set(r['methods'])}, "
                     f"pinned {want}")

        def round2():
            proj0 = engine.metrics.restore_project_wall
            for r, follow in zip(out, follows):
                sid = r["sid"]
                gen = _request(pump, Request(
                    sid, follow, max_new_tokens=NEW_TOKENS))
                seq = engine.sessions[sid]
                if not seq.restored or seq.history_len != r["n_hist"]:
                    fail(f"{sid} was not restored from the host store")
                r["round2"] = gen
                r["logits"] = logits[sid]
                r["restore_wall"] = seq.restore_wall
            if engine.metrics.restore_project_wall <= proj0:
                fail("no restore projection ran")
        phases.run("round2_restore", round2)

        def twin_round():
            for i, (r, follow) in enumerate(zip(out, follows)):
                tid = f"twin{i}"
                gen = _request(pump, Request(
                    tid, np.concatenate([r["history"], follow]),
                    max_new_tokens=1))
                r["twin_logits"] = logits[tid]
                r["twin_token"] = gen[0]
        if twins:
            phases.run("round2_twins", twin_round)
    finally:
        fd.close()
    return out


# ---------------------------------------------------------------- checks
def greedy_agrees(token: int, ref: np.ndarray) -> bool:
    """``token`` is the reference's greedy choice — or, when the
    reference's two best logits are closer than ``2 * LOGIT_ATOL``, one
    of its near-tied best choices: logits that agree within the
    tolerance may then rank them either way, on either path."""
    top2 = np.sort(ref)[-2:]
    if top2[1] - top2[0] >= 2 * LOGIT_ATOL:
        return token == int(np.argmax(ref))
    return bool(ref[token] >= top2[1] - 2 * LOGIT_ATOL)


def check_restores(results) -> None:
    for r in results:
        lg, ref = r["logits"], r["twin_logits"]
        tok = r["round2"][0]
        delta = float(np.max(np.abs(lg - ref)))
        top2 = np.sort(ref)[-2:]
        counts = {m: r["methods"].count(m) for m in sorted(set(r["methods"]))}
        report(f"restore {r['sid']} (pin {r['pin']}): {r['n_hist']} "
               f"tokens, methods {counts}, restore wall "
               f"{r['restore_wall']:.3f} s, max|dlogit| {delta:.6f} "
               f"(max|logit| {float(np.max(np.abs(ref))):.4f}, twin "
               f"top-2 margin {float(top2[1] - top2[0]):.6f}), greedy "
               f"{tok} vs twin {r['twin_token']}")
        if not np.all(np.isfinite(lg)):
            fail(f"{r['sid']}: non-finite logits")
        if delta > LOGIT_ATOL:
            fail(f"{r['sid']}: max|dlogit| {delta} > {LOGIT_ATOL}")
        if tok != int(np.argmax(lg)) or \
                r["twin_token"] != int(np.argmax(ref)):
            fail(f"{r['sid']}: a sampled token is not its logits' argmax")
        if not greedy_agrees(tok, ref):
            fail(f"{r['sid']}: greedy token {tok} != twin "
                 f"{r['twin_token']}")


def check_mosaic(engine, n_tokens: int) -> None:
    """The projection the executor dispatches, lowered with the same
    static arguments it passes: it must contain the Mosaic kernel."""
    import jax
    import jax.numpy as jnp
    from repro.core.restoration import _project_group_jit, s_bucket
    from repro.kernels import ops
    mgr = engine.mgr
    pack = mgr.param_pack(engine.params)
    G = mgr.restore_group_size if isinstance(mgr.restore_group_size,
                                             int) else 8
    S = s_bucket(n_tokens)
    cos, sin = pack.rope_tables(S)
    text = _project_group_jit.lower(
        jax.ShapeDtypeStruct((G, S, engine.model.cfg.d_model),
                             jnp.dtype(mgr.store_dtype)),
        jax.ShapeDtypeStruct((G,), jnp.int32),
        pack.ln_scale, pack.ln_bias, pack.wk, pack.wv, pack.bk, pack.bv,
        cos, sin, norm_kind=pack.norm_kind, eps=pack.norm_eps,
        head_dim=pack.head_dim, use_rope=pack.use_rope, dtype=pack.dtype,
        use_pallas=ops.on_tpu(), interpret=None,
        kv_sharding=pack.out_sharding).as_text()
    if "tpu_custom_call" not in text:
        fail("the restore projection did not lower to the Mosaic kernel")
    report(f"restore projection: Mosaic kernel present "
           f"(tpu_custom_call, G={G}, S={S}, "
           f"tp={engine.tp.tp if engine.tp.spmd else 1})")


def device_bytes(tag: str) -> None:
    import jax
    live = sum(a.nbytes for a in jax.live_arrays())
    report(f"{tag}: live jax arrays {live} bytes")
    for d in jax.local_devices():
        st = d.memory_stats() or {}
        report(f"{tag} device {d.id}: bytes_in_use "
               f"{st.get('bytes_in_use', 'n/a')}, peak_bytes_in_use "
               f"{st.get('peak_bytes_in_use', 'n/a')}, bytes_limit "
               f"{st.get('bytes_limit', 'n/a')}")


# ---------------------------------------------------------------- phases
@dataclasses.dataclass
class Size:
    """How big a run is: the chip defaults, or a few tokens for a CPU
    rehearsal of the same code."""
    pages: int = PAGES
    max_seq: int = MAX_SEQ
    prompt_lo: int = 2048
    prompt_hi: int = 4096
    follow: int = 64


def one_chip(cfg, seed: int, phases: Phases, size: Size = Size(), *,
             mosaic: bool = True) -> None:
    import jax
    engine = phases.run("build", make_engine, cfg, seed=seed, tp=1,
                        pages=size.pages, max_seq=size.max_seq)
    n_params = sum(x.size for x in jax.tree.leaves(engine.params))
    report(f"model {cfg.name}: {cfg.n_layers} layers, d_model "
           f"{cfg.d_model}, {cfg.n_heads}/{cfg.n_kv_heads} heads, d_ff "
           f"{cfg.d_ff}, vocab {cfg.vocab_size}, {n_params} params; pool "
           f"{engine.kv.num_blocks} pages of {engine.kv.block_size}")
    prompts, follows = make_traffic(cfg.vocab_size, seed, lo=size.prompt_lo,
                                    hi=size.prompt_hi, follow=size.follow)
    results = serve_traffic(engine, prompts, follows, phases)
    check_restores(results)
    if mosaic:
        check_mosaic(engine, max(r["n_hist"] for r in results))
    device_bytes("one-chip")


def four_chips(cfg, seed: int, phases: Phases, size: Size = Size(), *,
               mosaic: bool = True) -> None:
    """The same traffic at tp=4 and at tp=1 (one engine at a time: each
    holds a full copy of the weights on device 0), outputs compared."""
    import gc
    prompts, follows = make_traffic(cfg.vocab_size, seed, lo=size.prompt_lo,
                                    hi=size.prompt_hi, follow=size.follow)
    runs = {}
    for tp in (4, 1):
        engine = phases.run(f"build_tp{tp}", make_engine, cfg, seed=seed,
                            tp=tp, pages=size.pages, max_seq=size.max_seq)
        runs[tp] = serve_traffic(engine, prompts, follows, phases,
                                 twins=False)
        if mosaic:
            check_mosaic(engine, max(r["n_hist"] for r in runs[tp]))
        device_bytes(f"tp={tp}")
        del engine
        gc.collect()
        device_bytes(f"tp={tp} released")
    for a, b in zip(runs[4], runs[1]):
        delta = float(np.max(np.abs(a["logits"] - b["logits"])))
        report(f"tp=4 vs tp=1 {a['sid']} (pin {a['pin']}): round-1 "
               f"tokens equal {a['round1'] == b['round1']}, round-2 "
               f"tokens equal {a['round2'] == b['round2']}, "
               f"max|dlogit| {delta:.6f}")
        if a["methods"] != b["methods"]:
            fail(f"{a['sid']}: methods differ across tp")
        if a["round1"] != b["round1"] or \
                not greedy_agrees(a["round2"][0], b["logits"]):
            fail(f"{a['sid']}: tokens differ across tp")
        if delta > LOGIT_ATOL:
            fail(f"{a['sid']}: tp=4 vs tp=1 max|dlogit| {delta}")


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = p.parse_args()
    src = Path(__file__).resolve().parent / "src"
    if not (src / "repro").is_dir():
        fail(f"{src / 'repro'} not found: run from a checkout of the "
             f"repository")
    sys.path.insert(0, str(src))
    import jax
    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        fail(f"JAX found no TPU (platform {d0.platform!r})")
    if len(devices) < args.chips:
        fail(f"--chips {args.chips} but {len(devices)} device(s) visible")
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    phases = Phases(CompileClock())
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips(smoke_config(), SEED, phases)
    else:
        one_chip(smoke_config(), SEED, phases)
    comp = sum(c for _, _, c in phases.rows)
    report(f"total wall {time.perf_counter() - t0:.3f} s, backend compile "
           f"{comp:.3f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
