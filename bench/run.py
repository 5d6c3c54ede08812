#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload qwen2-7b.returning --seed 7 \
        --seconds 30 --trace 0

The cell (a configuration and a traffic mix) is named in BENCHMARK.json;
its files live under bench/configs, bench/traffic and bench/metrics.
The run builds the serving stack with weights made from the seed, opens
the mix's sessions, warms every program its schedule needs, drives the
front door open-loop for --seconds, follows the requests sent to their
end, checks a sample of the answers against a float32 reference and
prints one JSON object as the last line of standard output (with
--trace 1: the per-layer metrics and a profiler breakdown). The numbers
compared for ``correct`` are the last lines of standard error.

It needs a TPU: without one it exits non-zero before any result.
"""
import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE_DIR = os.path.join(HERE, ".jax_cache")
TRACE_DIR = os.path.join(HERE, ".trace")


def configure_jax():
    """JAX with its persistent compilation cache at the checkout's fixed
    path, every program persisted (thresholds 0)."""
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


def fail(msg: str, code: int = 2) -> None:
    print(f"bench/run.py: {msg}", file=sys.stderr, flush=True)
    sys.exit(code)


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args()
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        fail("the program (src/repro) is not in this checkout")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, ROOT)
    from bench.harness.cell import load_cell, run
    cell = load_cell(args.workload)
    jax = configure_jax()
    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        fail(f"JAX found no TPU (platform {d0.platform!r})")
    if len(devices) < cell.chips:
        fail(f"{args.workload} needs {cell.chips} chips, JAX sees "
             f"{len(devices)}")
    trace_dir = None
    if args.trace:
        trace_dir = os.path.join(TRACE_DIR, args.workload)
        shutil.rmtree(trace_dir, ignore_errors=True)
    print(f"device: platform {d0.platform}, kind {d0.device_kind}, "
          f"count {len(devices)}", flush=True)
    out = run(cell, args.seed, args.seconds, trace=bool(args.trace),
              t_start=T_START, trace_dir=trace_dir,
              device_kind=d0.device_kind)
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": cell.chips,
              "memory_peak_bytes": out.pop("memory_peak_bytes")}
    if args.trace:
        device["busy_s"] = out.pop("busy_s", 0.0)
        device["window_s"] = out.pop("trace_window", 0.0)
    checks = out.pop("checks")
    result = {"correct": out["correct"], "attempted": out["attempted"],
              "failed": out["failed"], "metrics": out["metrics"],
              "device": device}
    if "breakdown" in out:
        result["breakdown"] = out["breakdown"]
    result["checks"] = {name: {"value": v, "limit": lim}
                        for name, v, lim in checks if lim is not None}
    for name, v, lim in checks:
        print(f"check {name}: {v}" + ("" if lim is None
                                      else f" (limit {lim})"),
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
