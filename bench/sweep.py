#!/usr/bin/env python3
"""Offer a cell's traffic at several fixed rates, one after another in
one process, to find the highest rate the system sustains (the knee).

    python3 bench/sweep.py --workload opt-30b.returning --seed 5 \
        --seconds 30 --rates 0.3 0.5 0.8

Each rate is a whole run of the cell (its own weights, sessions and
warm-up) with the traffic file's ``rate_per_s`` replaced, and without
the reference check. Per rate it prints the requests due and answered,
TTFT quartiles, the TTFT of the last quarter of the requests against
the first (a growing backlog shows there), tokens per second and how
full the page pool got. Rates run in the order given and stop at the
first that does not sustain (a request unanswered, or the last quarter's
mean TTFT 2 s or more above the first's); the last line names the
highest rate sustained (the knee) and four fifths of it. Needs a TPU,
like ``run.py``.
"""
import argparse
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))
sys.path.insert(0, os.path.dirname(HERE))


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--rates", type=float, nargs="+", required=True)
    args = p.parse_args()
    from bench.run import configure_jax
    from bench.harness.cell import load_cell, run
    jax = configure_jax()
    if jax.devices()[0].platform != "tpu":
        sys.exit("bench/sweep.py: JAX found no TPU")
    knee = None
    for rate in args.rates:
        cell = load_cell(args.workload)
        cell.mix["rate_per_s"] = rate
        out = run(cell, args.seed, args.seconds, trace=False,
                  t_start=time.time(), check=False)
        print(f"SWEEP {args.workload} rate {rate}: {out['sweep']}; pool "
              f"{out['pool']}; sustained {out['sustained']}", flush=True)
        if not out["sustained"]:
            break
        knee = rate
    print(f"KNEE {args.workload} {knee} RATE "
          f"{None if knee is None else 0.8 * knee}", flush=True)


if __name__ == "__main__":
    main()
