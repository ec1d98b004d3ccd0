#!/usr/bin/env python3
"""Readings for the correctness limits: run one cell on many seeds in one
process and print what the comparison read on each.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 4
    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 4 --control

The readings are the program's own (the lower reading of each limit is
the largest over a dozen seeds or more).  With ``--control`` each seed
also reads the control over the same answers: the plain reference in
bfloat16, the nearest precision below the float32 the configurations
state, put in the program's place (the upper reading is the smallest
it gives).  The program's own bfloat16 path cannot serve: Mosaic
refuses its kernels on a TPU v5e.  Each seed prints one JSON line; the benchmark's own runs never run this.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

from benchlib.harness import chip_cell, measure  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args()
    t_start = T_START
    cell = chip_cell(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        out = measure(cell, seed, args.seconds, False, t_start,
                      control=args.control).result
        t_start = time.perf_counter()
        print(json.dumps(dict(
            workload=args.workload, seed=seed, control=args.control,
            correct=out["correct"], checks=out["checks"],
            compared=out["compared"], metrics=out["metrics"],
            control_reading=out.get("control"),
        )), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
