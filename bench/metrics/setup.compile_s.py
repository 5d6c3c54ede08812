"""Backend compile seconds during set-up (JAX's monitoring events;
loads from the persistent cache count their retrieval)."""


def read(ctx):
    return ctx.setup_compile_s
