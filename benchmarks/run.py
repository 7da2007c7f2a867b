"""Benchmark harness entry point — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV; writes results/*.json consumed by
EXPERIMENTS.md plus BENCH_interact.json / BENCH_graph.json /
BENCH_drift.json / BENCH_serve.json / BENCH_retrieval.json /
BENCH_faults.json / BENCH_churn.json / BENCH_experiment.json /
BENCH_precision.json at the repo root (the engine perf trajectories,
tracked per PR).

``--quick`` runs the fused-interaction microbenchmark at reduced
shapes/repeats, the stage-2 graph bench (full n sweep — its acceptance
gates live at n=16k/64k — with trimmed repeats), the non-stationary
drift scenario through the unified engine (single-host + 8-device
sharded), the online-serving transaction bench, the catalog-scale
retrieval bench (streaming top-K incl. the 2**20-item reference row +
8-device item-sharded transaction), the seeded fault-injection
bench (delayed/lossy feedback vs its clean control), the catalog
churn bench (double-buffered swaps under live traffic vs the churn-free
control), and the online-experimentation bench (Thompson-sampling
meta-selector vs the best fixed arm + routing overhead vs a bare
session), and the reduced-precision parity bench (modeled HBM cuts +
choice-flip rate vs the f32 oracle); a few minutes on one CPU core, and
still emits every BENCH_*.json, so CI can track the hot-path trends
cheaply and gate the modeled metrics (``benchmarks.check_regression``).

Failure policy: every sub-benchmark runs even if an earlier one fails,
but any failure makes the harness exit non-zero and name the culprits —
CI's quick-bench step is a real gate, not best-effort.  Each
sub-benchmark also runs under a wall-clock timeout (``--bench-timeout``,
SIGALRM-based, so a hung jax compile or subprocess counts as a failure
instead of wedging CI; no-op on platforms without SIGALRM).
"""
from __future__ import annotations

import argparse
import importlib
import signal
import sys
import traceback


def _bench_list(quick: bool):
    # each module is imported lazily INSIDE its runner so an import-time
    # error in one bench is reported/isolated like any other failure —
    # the remaining benches still run
    def runner(mod: str, **kw):
        def call():
            m = importlib.import_module(f".{mod}", __package__)
            return m.main(**kw)
        return call

    names = ["bench_interact", "bench_graph", "bench_drift", "bench_serve",
             "bench_retrieval", "bench_faults", "bench_churn",
             "bench_experiment", "bench_precision"]
    benches = [(n, runner(n, quick=quick)) for n in names]
    if not quick:
        benches += [(n, runner(n)) for n in
                    ("bench_kernels", "bench_paper", "bench_scaling")]
    return benches


def _call_with_timeout(fn, seconds: int):
    """Run ``fn()`` under a SIGALRM deadline (main thread only; silently
    unenforced where SIGALRM doesn't exist, e.g. Windows)."""
    if seconds <= 0 or not hasattr(signal, "SIGALRM"):
        return fn()

    def _expired(signum, frame):
        raise TimeoutError(f"benchmark exceeded --bench-timeout={seconds}s")

    prev = signal.signal(signal.SIGALRM, _expired)
    signal.alarm(seconds)
    try:
        return fn()
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, prev)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="engine benches only (interact/graph/drift/serve/"
                         "retrieval/faults/churn/experiment/precision), "
                         "reduced shapes/repeats, a few minutes on one "
                         "CPU core")
    ap.add_argument("--bench-timeout", type=int, default=1800,
                    help="per-sub-benchmark wall-clock limit in seconds "
                         "(0 disables); a timeout is reported like any "
                         "other bench failure")
    args = ap.parse_args(argv)
    from repro.launch import compile_cache
    compile_cache.enable()

    print("name,us_per_call,derived")
    failures: list[str] = []
    for name, fn in _bench_list(args.quick):
        try:
            _call_with_timeout(fn, args.bench_timeout)
        except Exception:
            traceback.print_exc()
            failures.append(name)
    if failures:
        print(f"FAILED benchmarks: {', '.join(failures)}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
