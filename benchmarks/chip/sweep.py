#!/usr/bin/env python3
"""Find the knee of an open-loop serve cell once, by a sweep of fixed
rates in one process (set-up once, then one window per rate).

    python3 benchmarks/chip/sweep.py --workload <serve cell> \
        --rates 900 1000 1100 --seconds 10 --seed 5

One JSON line per rate: the offered rate, the completed rate, the
latency quantiles and how long the queue took to drain after the window
(a queue that grows all through the window drains for seconds).  The
cell's traffic file then fixes its rate at about 0.8 of the highest
rate that is sustained.
"""
from __future__ import annotations

import argparse
import json
import sys

import run as R             # this directory's run.py; puts the checkout on sys.path


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=1)
    a = ap.parse_args(argv)
    _, _, cfg, traffic, driver = R.prepare(a.workload)
    if traffic["kind"] != "open_loop":
        print("sweep: only open-loop cells have a knee", file=sys.stderr)
        return 2
    cell = driver.setup(cfg, traffic, a.seed)
    for rate in a.rates:
        cell.traffic = dict(traffic, rate_per_s=rate)
        win = driver.window(cell, a.seconds)
        e2e, counters, _, failed = driver.results(cell, win)
        print(json.dumps({
            "offered_per_s": rate, **e2e, "failed": failed,
            "drain_s": counters["window_s"] - a.seconds,
            "transactions": counters["transactions"],
            "refreshes": counters["refreshes"],
            "mean_batch": counters["requests"] / counters["transactions"],
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
