"""The system under test, built from a configuration file: the program's
model at the configuration's sizes, the benchmark's weights, the host
chunk store, the HCache manager, the paged engine, and the front door
(pump + router) — the same pieces ``launch/serve.py`` assembles."""
from __future__ import annotations

import dataclasses
import types
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import weights as W
from bench.harness.traffic import Schedule
from bench.references import dense


def program_arch(cfg: dict):
    """The program's ArchConfig for a configuration file, checked against
    the published sizes the reference reads."""
    from repro.configs import get_arch
    prog = cfg["program"]
    arch = dataclasses.replace(get_arch(prog["arch"]), **prog["overrides"])
    ref = dense.arch_from_config(cfg["config"])
    want = {"d_model": ref["d_model"], "n_heads": ref["n_heads"],
            "n_kv_heads": ref["n_kv_heads"], "d_ff": ref["d_ff"],
            "vocab_size": ref["vocab"], "n_layers": ref["n_layers"],
            "qkv_bias": ref["qkv_bias"],
            "use_rope": not ref["learned_positions"],
            "ffn_glu": ref["glu"], "ffn_activation": ref["act"],
            "norm": ref["norm"], "norm_eps": ref["eps"],
            "tie_embeddings": ref["tie_embeddings"]}
    if not ref["learned_positions"]:
        want["rope_theta"] = ref["rope_theta"]
    got = {k: getattr(arch, k) for k in want}
    bad = {k: (got[k], want[k]) for k in want if got[k] != want[k]}
    if bad or arch.head_dim_ != ref["head_dim"]:
        raise RuntimeError(f"program config differs from the published "
                           f"sizes (program, published): {bad}")
    return arch, ref


@dataclasses.dataclass
class Stack:
    cfg: dict
    ref: dict                    # the reference's normalized sizes
    model: object
    engine: object
    pump: object
    router: object
    fd: object
    weight_s: float = 0.0

    def close(self, force: bool = False) -> None:
        self.pump.close(force=force)
        self.engine.mgr.store.close()


def build(cfg: dict, seed: int, n_sessions: int) -> Stack:
    import time
    from repro.config.hardware import profile_for_device
    from repro.core.hcache import HCacheManager
    from repro.distributed.sharding import default_rules
    from repro.frontend import EnginePump, FrontDoor, SessionRouter
    from repro.launch.mesh import make_mesh
    from repro.models import Model
    from repro.serving import InferenceEngine
    from repro.storage import ChunkStore, make_array

    prog = cfg["program"]
    arch, ref = program_arch(cfg)
    mesh = make_mesh((1, 1), ("data", "model"))
    model = Model(arch, rules=default_rules(mesh), model_axis=1,
                  dtype=jnp.bfloat16, remat="none")
    layout = W.dense_layout(ref)
    abstract, _ = model.abstract_params()
    W.check_layout(abstract, layout)
    t0 = time.perf_counter()
    params = W.program_params(abstract, layout, seed, jnp.bfloat16)
    jax.block_until_ready(params)
    weight_s = time.perf_counter() - t0
    store = ChunkStore(make_array("ssd", 4), chunk_tokens=64)
    mgr = HCacheManager(model, store, hw=profile_for_device(jax.devices()[0]),
                        restore_group_size=prog["restore_group_size"])
    engine = InferenceEngine(model, params, mgr,
                             max_batch=prog["max_batch"],
                             max_seq=prog["max_seq"], backend="paged",
                             block_size=prog["block_size"],
                             cache_blocks=prog["cache_blocks"])
    pump = EnginePump(engine, max_pending=prog["max_pending"]).start()
    router = SessionRouter(engine, n_slots=max(8, n_sessions + 1),
                           block_size=prog["block_size"])
    fd = FrontDoor(pump, router)
    return Stack(cfg, ref, model, engine, pump, router, fd, weight_s)


COMPILE_THREADS = 4      # concurrent compiles in set-up (host memory)


def open_sessions(st: Stack, opened, entry: str) -> None:
    """Store every session's first exchange as the engine would after
    serving it: the history's KV and hidden states from the program's own
    prefill (one jitted call per session instead of the engine's eager
    128-token chunks, which cost seconds each in set-up), saved through
    the HCache manager; through the front door the router also binds the
    conversation to the session, as a finished first turn leaves it.
    Call on the pump thread."""
    engine, mgr = st.engine, st.engine.mgr
    ad = engine.adapter
    prefill = jax.jit(lambda p, t: ad.prefill(
        p, {"tokens": t}, capture_hidden=engine.save_hidden))
    # one program per distinct history length: traced here, compiled (or
    # loaded from the persistent cache) on a few threads at once
    lengths = sorted({len(o.history) for o in opened})
    lowered = {n: prefill.lower(engine.params, jax.ShapeDtypeStruct(
        (1, n), jnp.int32)) for n in lengths}
    with ThreadPoolExecutor(COMPILE_THREADS) as pool:
        compiled = dict(zip(lengths, pool.map(
            lambda n: lowered[n].compile(), lengths)))
    for i, o in enumerate(opened):
        if entry == "front_door":
            dec = st.router.route(o.prompt, o.conv)
            st.router.complete(dec, list(o.answer))
            o.sid = dec.session_id
        else:
            o.sid = f"s{i}"
        out = compiled[len(o.history)](engine.params,
                                       jnp.asarray(o.history)[None])
        mgr.save_prefill(o.sid, np.asarray(o.history), out, start=0)
    mgr.saver.drain()


# ------------------------------------------------------------- warm-up
WARM_SID = "__bench_warm__"


def _scratch_session(engine, n: int, methods) -> None:
    """A stored session of ``n`` zero tokens under ``methods``, in the
    dtypes the save path stores: hidden states in the store dtype, KV in
    the model's."""
    store = engine.mgr.store
    cfg = engine.model.cfg
    kvd = cfg.n_kv_heads * cfg.head_dim_
    h_dt, kv_dt = engine.mgr.store_dtype, np.dtype(engine.model.dtype)
    for li, m in enumerate(methods):
        store.append_tokens(WARM_SID, "h", li, 0,
                            np.zeros((n, cfg.d_model), h_dt))
        if m == "kv":
            for s in ("kvk", "kvv"):
                store.append_tokens(WARM_SID, s, li, 0,
                                    np.zeros((n, kvd), kv_dt))
    store.flush(WARM_SID)
    store.put_blob(WARM_SID, "tok", 0, np.zeros((n,), np.int32))
    store.put_manifest(WARM_SID, {"n_tokens": n, "methods": list(methods),
                                  "arch": cfg.name, "compress": "none"})


def warm_shapes(engine, schedule: Schedule, methods) -> dict:
    """Run, once each, every device program that the window's schedule
    will ask for: each restore length, each (chunk, history) prefill
    shape, and each (pages, answer) retire shape — on a scratch slot and
    a scratch stored session, through the engine's own objects. Call on
    the pump thread while the engine is idle."""
    from repro.serving.kv_cache import ViewSink
    kv, mgr, ad = engine.kv, engine.mgr, engine.adapter
    if any(s is not None for s in engine.slots):
        raise RuntimeError("warm-up needs an idle engine")
    if schedule.align != engine.prefill_chunk:
        raise ValueError(f"traffic align {schedule.align} must equal the "
                         f"engine's prefill chunk {engine.prefill_chunk}")
    slot = 0
    bs = kv.block_size
    turns = schedule.turns
    top = max(t.prompt_end + t.answer_tokens for t in turns)
    _scratch_session(engine, top, methods)
    counts = {"restores": 0, "prefills": 0, "retires": 0}
    try:
        if not kv.reserve(slot, top):
            raise RuntimeError(f"page pool cannot hold a {top}-token "
                               f"scratch session")
        view = kv.view(slot)
        stub = types.SimpleNamespace(view=view)
        shapes = {}
        for c, h in schedule.prefill_shapes():
            shapes.setdefault(h, []).append(c)
        starts = {t.history for t in turns}
        man = mgr.store.get_manifest(WARM_SID)
        # longest first: the rotary table grows to its final size before
        # any slice of it is taken
        for n in sorted(starts, reverse=True):
            if n:
                mgr.store.put_manifest(WARM_SID, dict(man, n_tokens=n))
                ex = mgr.begin_restore(engine.params, WARM_SID)
                ex.attach_sink(ViewSink(view))
                ex.run()
                counts["restores"] += 1
        for h in sorted(shapes):
            for c in shapes[h]:
                mgr.store.put_manifest(WARM_SID, dict(man, n_tokens=h))
                chunk = np.zeros((c,), np.int32)
                out = ad.prefill_chunk(engine.params, stub, chunk, h,
                                       capture_hidden=engine.save_hidden)
                ad.absorb_prefill(view, out, c, h)
                view.set_length(h + c)
                mgr.save_prefill(WARM_SID, chunk, out, start=h)
                np.asarray(out["logits"][0, -1].argmax())
                counts["prefills"] += 1
        retire = sorted({(-(-(t.prompt_end + t.answer_tokens) // bs),
                          t.prompt_end, t.answer_tokens) for t in turns})
        seen = set()
        for pages, end, a in retire:
            if (pages, a) in seen:
                continue
            seen.add((pages, a))
            kv.free_slot(slot)
            kv.reserve(slot, pages * bs)
            mgr.store.put_manifest(WARM_SID, dict(man, n_tokens=end))
            mgr.save_session_pause(
                WARM_SID, view.snapshot(), end + a - 1,
                tokens_tail=np.zeros((a - 1,), np.int32))
            counts["retires"] += 1
        mgr.saver.drain()
    finally:
        kv.free_slot(slot)
        mgr.evict(WARM_SID)
    return counts
