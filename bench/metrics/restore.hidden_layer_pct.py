"""Share of restored (session, layer) pairs that the scheduler's plan
restores by projecting saved hidden states. The kv and recompute shares
are printed beside it."""


def shares(ctx):
    pairs = [m for t, _, methods in ctx.rec.restores
             if ctx.window[0] <= t <= ctx.window[1] for m in methods]
    if not pairs:
        return None
    return {k: 100.0 * pairs.count(k) / len(pairs)
            for k in ("hidden", "kv", "recompute")}


def read(ctx):
    sh = shares(ctx)
    if sh is None:
        return None
    print(f"restored layers by method: hidden {sh['hidden']:.3f}%, kv "
          f"{sh['kv']:.3f}%, recompute {sh['recompute']:.3f}%", flush=True)
    return sh["hidden"] or None
