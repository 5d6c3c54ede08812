"""Peak device memory in use over the run (``peak_bytes_in_use``), read
after the window, before the reference runs."""


def read(ctx):
    if ctx.mem_peak is None:
        return None
    return ctx.mem_peak / 1e9
