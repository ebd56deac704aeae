#!/usr/bin/env python3
"""Readings that the limits of `correct` are set from, on the chip.

    python3 bench/calibrate.py --workload <cell> --seconds <s> \\
        --seeds <n> [<n> ...] --control-seeds <n> [<n> ...]

In one process, runs the cell's window on each of `--seeds` with the
program as it is, on each of `--control-seeds` with the control in the
program's place (the plain reference one precision step below the
configuration's), and on each of `--fault-seeds` with `--fault` planted.
Prints one JSON line per run with the compared numbers.
Not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--fault", default="unchanged",
                    help="the fault planted on --fault-seeds")
    ap.add_argument("--fault-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)
    from bench import harness
    from bench.run import _enable_compile_cache
    import jax
    if jax.devices()[0].platform != "tpu":
        print("calibrate: no TPU found", file=sys.stderr)
        return 2
    _enable_compile_cache()
    cell = harness.resolve(args.workload, ROOT)
    loop = cell.loop()
    runs = ([(s, "program", None) for s in args.seeds]
            + [(s, "control", loop.plant_control)
               for s in args.control_seeds]
            + [(s, "fault:" + args.fault, loop.plant_fault(args.fault))
               for s in args.fault_seeds])
    for seed, what, plant in runs:
        t0 = time.perf_counter()
        run = loop.run(cell, seed=seed, seconds=args.seconds,
                         trace_dir=None, t_start=t0, plant=plant)
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "program": what,
                          "correct": run["correct"], "checks": run["checks"],
                          "notes": run["notes"], "e2e": run["e2e"]}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
