"""Restoration wall time per thousand restored tokens over the window:
the engine's ``restore_wall_sum`` over ``restored_tokens`` (both summed
by the engine when a restore completes; the executor drains its reads
before it reports done)."""


def read(ctx):
    n = ctx.counters["restored_tokens"]
    if n <= 0:
        return None
    return ctx.counters["restore_wall_sum"] * 1e3 / (n / 1e3)
