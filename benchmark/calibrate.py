#!/usr/bin/env python3
"""Readings for the limits of ``correct``: the program's numbers over
many seeds, the control's (the reference in float8) and the planted
faults', each compared with the float32 reference as a run compares.

    python3 benchmark/calibrate.py --workload <cell> --seeds 12 \
        --others 3 [--seconds 20] [--first-seed 1000]

One process, one JSON line per seed and kind on standard output.  Not
part of a benchmark run: the limits in ``benchmark/limits/`` are set
from what this prints on the chip (PERF.md gives the readings).
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--others", type=int, default=3,
                    help="seeds on which the control and the faults are read")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--first-seed", type=int, default=1000)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    from benchmark import harness
    from benchmark.run import _enable_compile_cache

    cell = harness.resolve(args.workload, rehearse=args.rehearse)
    driver_mod = harness.load_driver(cell.traffic["driver"])
    import jax

    _enable_compile_cache()
    if jax.devices()[0].platform != "tpu" and not args.rehearse:
        print("calibrate: no TPU", file=sys.stderr)
        return 2
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        for row in driver_mod.calibrate(cell, seed, args.seconds,
                                        others=i < args.others,
                                        rehearse=args.rehearse):
            print(json.dumps(dict(row, seed=seed)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
