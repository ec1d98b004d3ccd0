#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout.  The cells, their metrics and their bounds
are in ``BENCHMARK.json``; a cell's configuration, traffic and per-layer
readers are files under ``bench/`` found by name (``benchlib/spec.py``).
The last line of standard output is the run's JSON result; without a
TPU the command exits non-zero and prints none.
"""
import time

T_START = time.perf_counter()

import sys  # noqa: E402

from benchlib.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_start=T_START))
