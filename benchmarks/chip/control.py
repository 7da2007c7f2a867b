#!/usr/bin/env python3
"""Readings that set the limits of ``correct``: the program's numbers over
many seeds (the lower reading) and the control's (the upper reading), in
one process so that set-up compiles once.

    python3 benchmarks/chip/control.py --workload paper-distclub \
        --seeds 11 12 13 --seconds 4 [--control-seeds 11 12 13]

For each seed: set up the cell, run a window of ``--seconds`` at the
cell's own load, compare what the program produced with the reference
(``check``), and, for the control seeds, compare the reference computed
one precision step below (three bf16 passes in place of exact f32
products) with the reference (``control``).  Each side is judged against
the configuration's limits as a run judges itself; one JSON line per
seed.  The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import sys

import run as R             # this directory's run.py; puts the checkout on sys.path


def readings(workload, seeds, seconds, control_seeds=()):
    _, _, cfg, traffic, driver = R.prepare(workload)
    for seed in seeds:
        cell = driver.setup(cfg, traffic, seed)
        win = driver.window(cell, seconds)
        _, counters, _, failed = driver.results(cell, win)
        driver.release(cell, win)
        prog = driver.check(cell, win)
        row = {"seed": seed, "failed": failed, "program": prog,
               "program_correct": R.judge(prog, cfg["limits"], failed)[0]}
        if seed in control_seeds:
            ctl = driver.control(cell, win)
            row["control"] = ctl
            row["control_correct"] = R.judge(ctl, cfg["limits"], 0)[0]
        row["counters"] = {k: v for k, v in counters.items()
                           if not isinstance(v, list)}
        print(json.dumps(row, default=float), flush=True)
        del cell, win


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--seconds", type=float, default=4.0)
    a = ap.parse_args(argv)
    readings(a.workload, a.seeds, a.seconds, set(a.control_seeds))
    return 0


if __name__ == "__main__":
    sys.exit(main())
