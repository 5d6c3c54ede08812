"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows. Run:
    PYTHONPATH=src python -m benchmarks.run [--only fig9,fig12] [--smoke]
    PYTHONPATH=src python -m benchmarks.run --mode bench_restoration

``--smoke`` runs the fast analytic suites only (CI gate). ``--mode X``
runs one special-mode entry and writes its ``BENCH_X.json`` artifact.
Everything — figure suites, smoke membership, mode names, artifacts —
is enumerated from the single ``REGISTRY`` below, so a new mode can't
be silently skipped by a stale hand-maintained list.
"""
from __future__ import annotations

import argparse
import sys
import time

# One entry per benchmark module. Fields:
#   label    — figure-suite label; present iff the module has a ``run()``
#              the default full sweep should execute
#   smoke    — label runs under --smoke (fast analytic, no forward pass)
#   mode     — ``--mode`` name; present iff the module has a special mode
#   entry    — the mode's entry function (writes ``artifact``)
#   artifact — JSON file the mode emits (CI uploads exactly these)
REGISTRY = [
    dict(label="fig1/kernels", module="benchmarks.bench_kernels"),
    dict(label="fig9/fig10 TTFT", module="benchmarks.bench_restoration",
         smoke=True, mode="bench_restoration",
         entry="run_pipeline_comparison", artifact="BENCH_restoration.json",
         help="blocking vs pipelined restoration TTFT"),
    dict(label="fig11 sensitivity", module="benchmarks.bench_sensitivity",
         smoke=True),
    dict(label="fig12 scheduler ablation", module="benchmarks.bench_sched",
         smoke=True, mode="bench_sched", entry="run_sched_bench",
         artifact="BENCH_sched.json",
         help="static vs calibrated vs fetch-aligned restore plans "
              "under 1/2/4-way concurrency"),
    dict(label="fig13 partition methods", module="benchmarks.bench_partition",
         smoke=True),
    dict(label="fig14 two-stage saving", module="benchmarks.bench_two_stage"),
    dict(label="fig15 kv reuse", module="benchmarks.bench_kv_reuse"),
    dict(label="table3 storage cost",
         module="benchmarks.bench_storage_cost", smoke=True),
    dict(module="benchmarks.bench_capacity", mode="bench_capacity",
         entry="run_capacity_comparison", artifact="BENCH_capacity.json",
         help="eviction-policy + host-budget bake-off"),
    dict(module="benchmarks.bench_paged", mode="bench_paged",
         entry="run_paged_comparison", artifact="BENCH_paged.json",
         help="paged vs contiguous KV layouts at equal cache memory"),
    dict(module="benchmarks.bench_restore_batch", mode="bench_restore_batch",
         entry="run_restore_batch", artifact="BENCH_restore_batch.json",
         help="grouped-restoration group-size sweep"),
    dict(module="benchmarks.bench_encdec", mode="bench_encdec",
         entry="run_encdec_bench", artifact="BENCH_encdec.json",
         help="batched vs sequential whisper serving and "
              "restore-vs-recompute TTFT"),
    dict(module="benchmarks.bench_prefix", mode="bench_prefix",
         entry="run_prefix_comparison", artifact="BENCH_prefix.json",
         help="prefix sharing on vs off at an equal page pool"),
    dict(module="benchmarks.bench_slo", mode="bench_slo",
         entry="run_slo_bench", artifact="BENCH_slo.json",
         help="front-door SLO harness: steered vs route-blind "
              "multi-tenant mix (DESIGN.md §14)"),
    dict(module="benchmarks.bench_distributed", mode="bench_distributed",
         entry="run_distributed_bench", artifact="BENCH_distributed.json",
         help="sharded restore across {1,2,4} hosts x both placements + "
              "sync vs async IO on real file reads (DESIGN.md §15)"),
    dict(module="benchmarks.bench_tp", mode="bench_tp",
         entry="run_tp_bench", artifact="BENCH_tp.json",
         help="tensor-parallel restore at tp={1,2,4}: modeled projection "
              "speedup + served byte-identity (DESIGN.md §16)"),
]

MODES = {e["mode"]: e for e in REGISTRY if "mode" in e}


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--only", default=None,
                   help="comma-separated substring filters")
    p.add_argument("--smoke", action="store_true",
                   help="fast analytic suites only (CI)")
    p.add_argument("--mode", default=None, choices=sorted(MODES),
                   help="special modes: " + "; ".join(
                       f"{m} — {e.get('help', e['entry'])} -> "
                       f"{e['artifact']}" for m, e in sorted(MODES.items())))
    args = p.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    print("name,us_per_call,derived")
    if args.mode:
        e = MODES[args.mode]
        mod = __import__(e["module"], fromlist=[e["entry"]])
        rows = getattr(mod, e["entry"])()
        print(f"# {len(rows)} rows -> {e['artifact']}", file=sys.stderr)
        return
    filters = args.only.split(",") if args.only else None
    t0 = time.time()
    n_rows = 0
    for e in REGISTRY:
        label = e.get("label")
        if label is None:
            continue
        module = e["module"]
        if filters and not any(f in label or f in module for f in filters):
            continue
        if args.smoke and not e.get("smoke"):
            continue
        print(f"# --- {label} ({module}) ---", file=sys.stderr)
        mod = __import__(module, fromlist=["run"])
        rows = mod.run()
        n_rows += len(rows)
    print(f"# {n_rows} rows in {time.time() - t0:.1f}s", file=sys.stderr)


if __name__ == "__main__":
    main()
