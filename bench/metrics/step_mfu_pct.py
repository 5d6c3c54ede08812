"""Model FLOP/s utilization of the whole serving step in the traced part
of the window: forward operations of the tokens served there (prompt
chunks and decoded tokens whose host span lies inside the trace;
restoration excluded), computed from shapes, over the trace's own window
times the chip's peak bf16 FLOP/s."""
from bench.harness import flops


def read(ctx):
    if ctx.traced is None or ctx.trace is None or ctx.peaks is None \
            or ctx.trace.window_s <= 0:
        return None
    lo, hi = ctx.traced
    f = sum(flops.prefill_flops(ctx.ref, c, h)
            for t0, t1, c, h in ctx.rec.prefill if lo <= t0 and t1 <= hi)
    f += sum(flops.decode_flops(ctx.ref, ctxs)
             for t0, t1, ctxs in ctx.rec.decode if lo <= t0 and t1 <= hi)
    if f <= 0:
        return None
    return 100.0 * f / (ctx.trace.window_s * ctx.peaks["bf16_flops_per_s"])
