#!/usr/bin/env python3
"""The knee of an open-loop mix: run it at several fixed rates in one
process and print, per rate, the latency percentiles, how late the
generator ran and whether the backlog grew over the window.

    python3 bench/sweep.py --config wifi-11a-rx --traffic steady --rates 100,150,200 --seconds 10

The knee is the highest rate whose backlog does not grow; a cell of the
mix fixes its rate at about four fifths of it.  The benchmark's own runs
never run this.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

from benchlib.harness import chip_cell, measure  # noqa: E402

LATENCY = [{"name": "latency_p50_ms", "unit": "ms"},
           {"name": "latency_p95_ms", "unit": "ms"}]


def backlog_trend(answers, t0: float, t_end: float) -> float:
    """Least-squares slope (requests/s) of the number of requests due and
    not yet held, sampled every 10 ms over the window."""
    due = np.array([a.due for a in answers])
    held = np.array([a.held if a.held is not None else np.inf
                     for a in answers])
    ts = np.arange(t0, t_end, 0.01)
    out = [(np.count_nonzero((due <= t) & (held > t))) for t in ts]
    return float(np.polyfit(ts - t0, out, 1)[0]) if len(ts) > 2 else 0.0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    t_start = T_START
    cell = chip_cell(f"{args.config}.{args.traffic}", dict(
        config=args.config, traffic=args.traffic, chips=1))
    cell.end_to_end += LATENCY
    for rate in (float(r) for r in args.rates.split(",")):
        cell.traffic["rate_per_s"] = rate
        m = measure(cell, args.seed, args.seconds, False, t_start)
        t_start = time.perf_counter()
        items = [a for a in m.driver.answers if a.in_window]
        late = m.driver.late
        print(json.dumps(dict(
            rate_per_s=rate,
            backlog_per_s=backlog_trend(items, m.t0, m.t_end),
            held_by_window_end=sum(
                a.held is not None and a.held <= m.t_end for a in items),
            due_in_window=len(items), correct=m.result["correct"],
            generator_late_p95_ms=1e3 * (statistics.quantiles(
                late, n=20)[18] if len(late) > 1 else late[0]),
            metrics=m.result["metrics"],
        )), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
