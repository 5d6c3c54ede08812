"""Named host spans inside the serving program (engine, pump, restoration
executor, save path).

``span(name)`` opens a ``jax.profiler.TraceAnnotation``: while a profiler
session records, the span lands in the same trace as the device
operations, on the same clock, so an idle gap of the device can be put
down to the host work that was running in it; with no session it costs
one small object and two clock reads. A span that feeds a counter also
adds its wall seconds to the float attribute ``field`` of ``into`` (an
``EngineMetrics``, or a restoration executor that the engine folds into
its metrics when the restore completes).

Names come from the fixed set below. Every name starts with ``engine.``
(trace readers keep host spans by that prefix) and nests under the
engine method it runs in.
"""
from __future__ import annotations

import time

from jax.profiler import TraceAnnotation
from jaxlib._profiler import TraceMe

ADMIT = "engine.admit"                      # admission, reservation, prefetch
PREFILL_GATHER = "engine.prefill.gather"    # resident history for a chunk
PREFILL_FORWARD = "engine.prefill.forward"  # the model call on a chunk
PREFILL_ABSORB = "engine.prefill.absorb"    # chunk KV into the cache
PREFILL_SAVE = "engine.prefill.save"        # chunk state to the host store
PREFILL_SAMPLE = "engine.prefill.sample"    # first token of the answer
DECODE_FORWARD = "engine.decode.forward"    # decode dispatch, length fix-up
DECODE_SAMPLE = "engine.decode.sample"      # sampled tokens read back
DECODE_SAVE = "engine.decode.save"          # hidden states to the saver
RESTORE_READ = "engine.restore.read"        # chunk-store reads, assembly
RESTORE_UPLOAD = "engine.restore.upload"    # host arrays to the device
RESTORE_WRITE = "engine.restore.write"      # restored pieces into the cache
RESTORE_PROJECT = "engine.restore.project"  # hidden states -> KV
RETIRE_SAVE = "engine.retire.save"          # KV tail of a leaving session
PUMP_WAIT = "engine.pump.wait"              # the pump idles for work
PUMP_CALL = "engine.pump.call"              # deferred calls on the pump

NAMES = frozenset((
    ADMIT, PREFILL_GATHER, PREFILL_FORWARD, PREFILL_ABSORB, PREFILL_SAVE,
    PREFILL_SAMPLE, DECODE_FORWARD, DECODE_SAMPLE, DECODE_SAVE,
    RESTORE_READ, RESTORE_UPLOAD, RESTORE_WRITE, RESTORE_PROJECT,
    RETIRE_SAVE, PUMP_WAIT, PUMP_CALL))

_recording = TraceMe.is_enabled


class span:
    """``with span(NAME[, into, field]):`` — see the module docstring."""

    __slots__ = ("name", "into", "field", "ann", "t0")

    def __init__(self, name: str, into=None, field: str = ""):
        if name not in NAMES:
            raise ValueError(f"unknown span name {name!r}")
        self.name = name
        self.into = into
        self.field = field

    def __enter__(self):
        # the annotation object is made only while a session records
        self.ann = TraceAnnotation(self.name) if _recording() else None
        if self.ann is not None:
            self.ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self.t0
        if self.ann is not None:
            self.ann.__exit__(*exc)
        if self.into is not None:
            setattr(self.into, self.field,
                    getattr(self.into, self.field) + dt)
