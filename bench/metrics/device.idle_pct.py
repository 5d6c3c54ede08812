"""Share of the traced window in which no operation ran on the device
(1 - union of device-operation intervals / window)."""
from bench.harness import trace


def read(ctx):
    if ctx.trace is None or ctx.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - trace.busy_s(ctx.trace) / ctx.trace.window_s)
