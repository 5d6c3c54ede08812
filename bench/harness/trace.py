"""Reduction of a profiler trace (``.xplane.pb``) to device busy and idle
time, time by device operation, and idle gaps named by the host span
that was open in them.

The traced window is the host span ``WINDOW_SPAN`` that the harness opens
around the traced part of a run; device and host events share the
trace's clock. Device operations are the events of the ``XLA Ops`` line
of each ``/device:`` plane.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"


@dataclasses.dataclass
class Event:
    name: str
    start: float          # seconds on the trace's clock
    end: float


@dataclasses.dataclass
class Trace:
    window: Tuple[float, float]
    devices: Dict[str, List[Event]]        # plane name -> ops in window
    spans: List[Event]                     # host spans in window

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]


def find_xplane(directory: str) -> str:
    found = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return found[-1]


def op_name(raw: str) -> str:
    """A device operation's stable name: the HLO instruction name
    (``restore_kv_grouped.1`` of ``%restore_kv_grouped.1 = (...)``)."""
    return raw.split(" = ", 1)[0].lstrip("%")


def _events(line, name=lambda n: n):
    for ev in line.events:
        s = ev.start_ns * 1e-9
        yield Event(name(ev.name), s, s + ev.duration_ns * 1e-9)


def load(path: str, host_spans: Optional[Sequence[str]] = None) -> Trace:
    """Read one trace. ``host_spans``: names (or ``prefix*``) of host
    events to keep; all host events when None."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    spans, devices, window = [], {}, None

    def keep(name):
        if host_spans is None:
            return True
        return any(name == p or (p.endswith("*") and name.startswith(p[:-1]))
                   for p in host_spans)

    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            ops = [e for line in plane.lines if line.name == OPS_LINE
                   for e in _events(line, op_name)]
            if ops:
                devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in _events(line):
                    if e.name == WINDOW_SPAN:
                        window = (e.start, e.end)
                    elif keep(e.name):
                        spans.append(e)
    if window is None:
        raise ValueError(f"trace {path} has no {WINDOW_SPAN!r} host span")
    lo, hi = window
    clip = lambda es: [Event(e.name, max(e.start, lo), min(e.end, hi))
                       for e in es if e.end > lo and e.start < hi]
    return Trace(window, {k: clip(v) for k, v in devices.items()},
                 clip(spans))


def merged(events: Sequence[Event]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for e in sorted(events, key=lambda e: e.start):
        if out and e.start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e.end)
        else:
            out.append([e.start, e.end])
    return [(a, b) for a, b in out]


def busy_s(trace: Trace) -> float:
    """Seconds in which some operation ran, averaged over devices."""
    if not trace.devices:
        return 0.0
    tot = [sum(b - a for a, b in merged(ops))
           for ops in trace.devices.values()]
    return sum(tot) / len(tot)


def op_seconds(trace: Trace) -> Dict[str, float]:
    """Device seconds by operation name, summed over devices / count."""
    out: Dict[str, float] = defaultdict(float)
    n = max(len(trace.devices), 1)
    for ops in trace.devices.values():
        for e in ops:
            out[e.name] += (e.end - e.start) / n
    return dict(out)


def kernel_seconds(trace: Trace, pattern: str) -> Tuple[float, int]:
    """(device seconds, event count) of operations whose name matches
    the regular expression ``pattern``, averaged over devices."""
    rx = re.compile(pattern)
    n = max(len(trace.devices), 1)
    secs, count = 0.0, 0
    for ops in trace.devices.values():
        for e in ops:
            if rx.search(e.name):
                secs += e.end - e.start
                count += 1
    return secs / n, count


def idle_gaps(trace: Trace, top: int = 10) -> List[Tuple[str, float]]:
    """The longest idle gaps of the first device, each named by the
    innermost host span open at its midpoint (``host idle`` if none)."""
    if not trace.devices:
        return []
    ops = trace.devices[sorted(trace.devices)[0]]
    lo, hi = trace.window
    edges = [lo] + [x for ab in merged(ops) for x in ab] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[1] - g[0], reverse=True)
    out = []
    for a, b in gaps[:top]:
        mid = (a + b) / 2
        open_ = [s for s in trace.spans if s.start <= mid <= s.end]
        name = (min(open_, key=lambda s: s.end - s.start).name
                if open_ else "host idle")
        out.append((name, b - a))
    return out


def breakdown(trace: Trace, top: int = 10) -> dict:
    ops = sorted(op_seconds(trace).items(), key=lambda kv: kv[1],
                 reverse=True)[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in idle_gaps(trace, top)]}
