"""Share of the traced window in which the device is idle while the
innermost open host span is one of the restoration executor's spans
(``engine.restore.*``: chunk-store reads and hidden-state assembly,
uploads, cache writes, the projection): how long the device waits on
restore work. The idle time is split by ``device.idle_prefill_pct``'s
``idle_by_span``, loaded from that file. Prints the idle seconds of each
restore span. None where the trace holds no restore span of the
program."""
import importlib.util
import os

PREFIX = "engine.restore."
SPLIT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "device.idle_prefill_pct.py")


def _idle_by_span():
    spec = importlib.util.spec_from_file_location(
        "bench_metric_device_idle_prefill_pct_split", SPLIT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.idle_by_span


def read(ctx):
    tr = ctx.trace
    if tr is None or tr.window_s <= 0 or \
            not any(s.name.startswith(PREFIX) for s in tr.spans):
        return None
    by = {k: v for k, v in _idle_by_span()(tr).items()
          if k.startswith(PREFIX)}
    split = ", ".join(f"{k} {v:.6f}" for k, v in
                      sorted(by.items(), key=lambda kv: -kv[1]))
    print(f"device idle under restore spans (s): {split or 'none'}",
          flush=True)
    return 100.0 * sum(by.values()) / tr.window_s
