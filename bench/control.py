#!/usr/bin/env python3
"""Readings that a cell's ``correct`` limit is set from, on the chip.

    python3 bench/control.py --workload qwen2-7b.returning \
        --seconds 20 --seeds 11 12 13

For each seed, one run of the cell with a short window, in one process:
the program's widest served-token gap below the float32 reference's best
logit (``max_logit_gap``, the number a run compares), and on the same
sample of prompts and served tokens the control's: the gap of the token
that the reference computed in float8 ranks first
(``control_logit_gap``). The limit in the configuration file lies
between the largest program reading and the smallest control reading;
judged against it, the program has to come out correct and the control
not (the script exits non-zero otherwise). The benchmark's own runs
never run the control.
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
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    args = p.parse_args()
    from bench.run import configure_jax
    from bench.harness.cell import load_cell, run
    jax = configure_jax()
    if jax.devices()[0].platform != "tpu":
        sys.exit("bench/control.py: JAX found no TPU")
    bad = []
    for seed in args.seeds:
        out = run(load_cell(args.workload), seed, args.seconds, trace=False,
                  t_start=time.time(), control=True)
        got = {name: v for name, v, _ in out["checks"]}
        ctl = out.get("control")
        gap = ctl["checks"][0][1] if ctl else None
        print(f"CONTROL {args.workload} seed {seed}: program "
              f"{got.get('max_logit_gap')} (correct {out['correct']}, "
              f"failed {out['failed']}) control {gap} (correct "
              f"{ctl['correct'] if ctl else None}) over "
              f"{got.get('tokens_compared')} tokens; pool "
              f"{out['pool']}", flush=True)
        if not out["correct"] or ctl is None or ctl["correct"]:
            bad.append(seed)
    if bad:
        sys.exit(f"bench/control.py: on seeds {bad} the program was not "
                 f"correct or the control was not caught")

if __name__ == "__main__":
    main()
