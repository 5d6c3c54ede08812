"""The restore projection kernel's share of its roofline in the traced
part of the window: the least time the chip could take for the kernel
calls recorded there (the larger of their operations over peak FLOP/s
and their bytes over peak bandwidth, from the call shapes) over the
device time of the kernel's events in the trace. Prints which bound
applies."""
from bench.harness import flops, trace

KERNEL = r"^restore_kv_grouped"


def read(ctx):
    if ctx.trace is None or ctx.peaks is None or ctx.traced is None:
        return None
    lo, hi = ctx.traced
    calls = [c for c in ctx.rec.projections
             if lo <= c[0] and c[1] <= hi and c[8]]
    secs, n = trace.kernel_seconds(ctx.trace, KERNEL)
    if not calls or secs <= 0:
        return None
    f = b = 0
    for _, _, G, S, D, KV, bias, rope, _ in calls:
        cf, cb = flops.restore_kv(G, S, D, KV, bias=bias,
                                  rope_head_dim=rope)
        f += cf
        b += cb
    t_f = f / ctx.peaks["bf16_flops_per_s"]
    t_b = b / ctx.peaks["hbm_bytes_per_s"]
    bound = "compute" if t_f >= t_b else "memory"
    print(f"restore_kv: {len(calls)} calls, {n} kernel events, "
          f"{secs:.6f} device s, {f} flops, {b} bytes, {bound}-bound "
          f"floor {max(t_f, t_b):.6f} s", flush=True)
    return 100.0 * max(t_f, t_b) / secs
