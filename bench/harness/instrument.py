"""Host spans and counters that the benchmark takes from outside the
program: wrappers around named engine calls, JAX's compile events, and
the device's memory counter.

Every wrapped name must exist: a missing one raises, so a refactor of
the program breaks the benchmark loudly instead of leaving a metric
silently empty.
"""
from __future__ import annotations

import dataclasses
import time
from collections import defaultdict
from typing import List, Optional

import jax

# (object attribute path on the engine, host span name)
ENGINE_SPANS = (
    ("step", "engine.step"),
    ("_restore_step", "engine.restore"),
    ("_prefill_step", "engine.prefill"),
    ("_decode_batch", "engine.decode"),
)
PROJECTION = ("repro.core.restoration", "_project_group_jit")


class CompileClock:
    """Backend compile seconds and persistent-cache hits and misses, from
    JAX's monitoring events. A miss is a compilation; a hit loads one."""

    def __init__(self):
        self.secs = defaultdict(float)
        self.counts = defaultdict(int)
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **_):
        self.secs[event] += duration
        self.counts[event] += 1

    def _event(self, event, **_):
        self.counts[event] += 1

    def snapshot(self) -> dict:
        return {"compile_s":
                self.secs["/jax/core/compile/backend_compile_duration"],
                "misses": self.counts["/jax/compilation_cache/cache_misses"],
                "hits": self.counts["/jax/compilation_cache/cache_hits"]}


@dataclasses.dataclass
class Record:
    decode: List[tuple] = dataclasses.field(default_factory=list)
    # (t0, t1, [contexts of active sequences])
    prefill: List[tuple] = dataclasses.field(default_factory=list)
    # (t0, t1, chunk, history)
    restores: List[tuple] = dataclasses.field(default_factory=list)
    # (t, session, methods)
    projections: List[tuple] = dataclasses.field(default_factory=list)
    # (t0, t1, G, S, D, KV, bias, rotary head_dim or 0, pallas)
    pages_peak: int = 0          # most pool pages held at a decode step
    live_peak: int = 0           # most sequences decoding at once


def _resolve(obj, path: str):
    for part in path.split("."):
        if not hasattr(obj, part):
            raise AttributeError(
                f"benchmark wraps {path!r}, which the program no longer "
                f"has ({type(obj).__name__} lacks {part!r})")
        obj = getattr(obj, part)
    return obj


class Instrument:
    """Wraps the engine's calls. ``annotate`` adds profiler host spans
    (traced runs only); recording is always on and cheap."""

    def __init__(self, engine, *, annotate: bool):
        import importlib
        self.engine = engine
        self.rec = Record()
        self.annotate = annotate
        self._undo = []
        mod = importlib.import_module(PROJECTION[0])
        # every name first, so that a missing one patches nothing
        for attr, _ in ENGINE_SPANS:
            _resolve(engine, attr)
        for path in ("adapter.prefill_chunk", "mgr.begin_restore",
                     "slots", "kv.num_blocks", "kv.allocator.free_count"):
            _resolve(engine, path)
        _resolve(mod, PROJECTION[1])
        for attr, span in ENGINE_SPANS:
            self._wrap(engine, attr, span)
        self._wrap_prefill(engine.adapter)
        self._wrap_decode(engine)
        self._wrap_restore(engine.mgr)
        self._wrap_projection(mod, PROJECTION[1])

    def _patch(self, obj, attr, fn):
        old = obj.__dict__.get(attr, None)
        setattr(obj, attr, fn)
        self._undo.append((obj, attr, old))

    def _wrap(self, obj, attr, span):
        inner = _resolve(obj, attr)
        if not self.annotate:
            return

        def wrapped(*a, **kw):
            with jax.profiler.TraceAnnotation(span):
                return inner(*a, **kw)
        self._patch(obj, attr, wrapped)

    def _wrap_prefill(self, adapter):
        inner = _resolve(adapter, "prefill_chunk")
        rec = self.rec

        def wrapped(params, seq, chunk, hist, **kw):
            t0 = time.perf_counter()
            out = inner(params, seq, chunk, hist, **kw)
            rec.prefill.append((t0, time.perf_counter(), len(chunk),
                                int(hist)))
            return out
        self._patch(adapter, "prefill_chunk", wrapped)

    def _wrap_decode(self, engine):
        inner = _resolve(engine, "_decode_batch")
        rec = self.rec
        decode_phase = None

        def wrapped():
            nonlocal decode_phase
            if decode_phase is None:
                from repro.serving.request import Phase
                decode_phase = Phase.DECODE
            # attention context of each active sequence after this step,
            # from the engine's host-side state (no device read)
            ctxs = [s.total_len for s in engine.slots
                    if s is not None and s.phase == decode_phase
                    and not s.finished()]
            kv = engine.kv
            rec.pages_peak = max(rec.pages_peak,
                                 kv.num_blocks - kv.allocator.free_count)
            rec.live_peak = max(rec.live_peak, len(ctxs))
            if not ctxs:
                return inner()
            t0 = time.perf_counter()
            out = inner()
            rec.decode.append((t0, time.perf_counter(), ctxs))
            return out
        self._patch(engine, "_decode_batch", wrapped)

    def _wrap_restore(self, mgr):
        inner = _resolve(mgr, "begin_restore")
        rec = self.rec

        def wrapped(params, session, *a, **kw):
            ex = inner(params, session, *a, **kw)
            rec.restores.append((time.perf_counter(), session,
                                 tuple(ex.methods)))
            return ex
        self._patch(mgr, "begin_restore", wrapped)

    def _wrap_projection(self, mod, name):
        inner = _resolve(mod, name)
        rec = self.rec

        def wrapped(hidden, rows, ln_scale, ln_bias, wk, wv, bk, bv, *a,
                    **kw):
            t0 = time.perf_counter()
            out = inner(hidden, rows, ln_scale, ln_bias, wk, wv, bk, bv,
                        *a, **kw)
            G, S, D = hidden.shape
            rope = kw.get("head_dim", 0) if kw.get("use_rope") else 0
            rec.projections.append(
                (t0, time.perf_counter(), G, S, D, wk.shape[-1],
                 bk is not None, int(rope), bool(kw.get("use_pallas"))))
            return out
        self._patch(mod, name, wrapped)

    def close(self) -> None:
        for obj, attr, old in reversed(self._undo):
            if old is None:
                try:
                    delattr(obj, attr)
                except AttributeError:
                    pass
            else:
                setattr(obj, attr, old)
        self._undo = []


def memory_peak() -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None
