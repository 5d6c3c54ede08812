"""Median wall time of the engine's synchronous decode step over the
window (host clock around the engine's decode call; the step ends in a
host read of the sampled tokens, so it includes the device work)."""
import statistics


def read(ctx):
    lo, hi = ctx.window
    d = [t1 - t0 for t0, t1, _ in ctx.rec.decode if lo <= t0 and t1 <= hi]
    if not d:
        return None
    return statistics.median(d) * 1e3
