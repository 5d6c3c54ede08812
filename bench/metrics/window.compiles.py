"""Programs compiled inside the window (persistent-cache misses from
JAX's monitoring events). Should be 0."""


def read(ctx):
    return float(ctx.window_compiles)
