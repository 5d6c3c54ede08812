"""Percentiles and rates over all requests of a window."""
from __future__ import annotations

import math
from typing import List, Sequence


def percentile(values: Sequence[float], p: float) -> float:
    """Nearest-rank percentile: the smallest value with at least ``p``%
    of the values at or below it."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    k = max(math.ceil(p / 100.0 * len(xs)), 1)
    return float(xs[k - 1])


def ttft_values(served, cap_s: float) -> List[float]:
    """Time to first token of every request from its due time; a request
    that failed or got no token counts as ``cap_s``."""
    out = []
    for s in served:
        if s.ok and s.times:
            out.append(s.times[0] - s.due)
        else:
            out.append(cap_s)
    return out


def gaps(served) -> List[float]:
    """Every gap between consecutive tokens of every request."""
    return [b - a for s in served for a, b in zip(s.times, s.times[1:])]


def tokens_in(served, t0: float, t1: float) -> int:
    return sum(1 for s in served for t in s.times if t0 <= t < t1)
