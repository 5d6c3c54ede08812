"""Share of the traced window in which the device is idle while the
innermost open host span is one of the program's prefill spans
(``engine.prefill.*``: history gather, model call, cache write, save,
sampling). Every idle interval of the device is split at span edges and
each piece is put down to the innermost span open in it (the shortest,
as the breakdown names its gaps). Prints the idle seconds by span name
over all gaps, the part no program span covers, and the share the
program's spans name. None where the trace holds no prefill span of the
program."""
import heapq
from collections import defaultdict

from bench.harness import trace
from bench.harness.instrument import ENGINE_SPANS

PREFIX = "engine.prefill."
OUTSIDE = frozenset(name for _, name in ENGINE_SPANS)  # bench wrappers
NONE = "no span"


def idle_intervals(tr):
    """Idle intervals of the first device in the traced window."""
    ops = tr.devices[sorted(tr.devices)[0]] if tr.devices else []
    lo, hi = tr.window
    edges = [lo] + [x for ab in trace.merged(ops) for x in ab] + [hi]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def idle_by_span(tr):
    """Idle seconds by the innermost span open in them (``NONE``: none
    open), over every idle interval of the window."""
    marks = []                  # (time, order, kind, index)
    for i, (a, b) in enumerate(idle_intervals(tr)):
        marks += [(a, 1, "idle", i), (b, 0, "busy", i)]
    for i, s in enumerate(tr.spans):
        marks += [(s.start, 1, "open", i), (s.end, 0, "close", i)]
    marks.sort()
    out = defaultdict(float)
    heap, closed, idle, t = [], set(), False, None
    for when, _, kind, i in marks:
        if idle and t is not None and when > t:
            while heap and heap[0][1] in closed:
                heapq.heappop(heap)
            out[tr.spans[heap[0][1]].name if heap else NONE] += when - t
        t = when
        if kind == "idle":
            idle = True
        elif kind == "busy":
            idle = False
        elif kind == "open":
            s = tr.spans[i]
            heapq.heappush(heap, (s.end - s.start, i))
        else:
            closed.add(i)
    return dict(out)


def read(ctx):
    tr = ctx.trace
    if tr is None or tr.window_s <= 0 or \
            not any(s.name.startswith(PREFIX) for s in tr.spans):
        return None
    by = idle_by_span(tr)
    total = sum(by.values())
    named = sum(v for k, v in by.items() if k != NONE and k not in OUTSIDE)
    split = ", ".join(f"{k} {v:.6f}" for k, v in
                      sorted(by.items(), key=lambda kv: -kv[1]))
    print(f"device idle by innermost span (s): {split}; program spans "
          f"name {named:.6f} of {total:.6f} idle s "
          f"({100.0 * named / total if total else 0.0:.1f}%)", flush=True)
    prefill = sum(v for k, v in by.items() if k.startswith(PREFIX))
    return 100.0 * prefill / tr.window_s
