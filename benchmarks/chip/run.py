#!/usr/bin/env python3
"""On-chip benchmark of the bandit system: one cell, one run, one process.

    python3 benchmarks/chip/run.py --workload paper-distclub --seed 7 \
        --seconds 10 --trace 0

The cell (``BENCHMARK.json``'s ``workloads``) names a configuration
(``configs/<name>.json``) and a traffic mix (``traffic/<name>.json``); the
mix's ``kind`` picks the driver (``drivers/<kind>.py``), and each per-layer
metric is read by ``metrics/<name>.py``.  A run builds its world from the
seed on the device, warms up the cell's own programs (``setup_s``),
measures for ``--seconds``, then compares what the timed path produced
with the plain reference (``reference/``) and prints, as the last line of
standard output, one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the end-to-end metrics with ``--trace 0``, the per-layer ones
with ``--trace 1``), ``device`` and, traced, ``breakdown``; ``checks``,
each compared number beside its limit, comes last, and the same numbers
close standard error.

It exits non-zero with no result line when JAX finds no TPU or fewer chips
than the cell asks for, when an engine resolves to anything but compiled
Pallas kernels, and when the program cannot be imported.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
if sys.path and pathlib.Path(sys.path[0] or ".").resolve() == HERE:
    sys.path.pop(0)                  # keep this directory's names private
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


class NoChip(RuntimeError):
    pass


def load_cell(workload: str):
    """``(bench, cell entry, config dict, traffic dict)`` of a workload,
    each found by its name."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg = json.loads((ROOT / configs[cell["config"]]["file"]).read_text())
    traffic = json.loads((HERE / "traffic" / f"{cell['traffic']}.json")
                         .read_text())
    return bench, cell, cfg, traffic


def load_reader(name: str):
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(bench, workload, kind):
    """The ``end_to_end`` or ``per_layer`` entries reported in a cell."""
    out = []
    for m in bench[kind]:
        if "workloads" in m and workload not in m["workloads"]:
            continue
        out.append(m)
    return out


def enable_cache():
    """JAX's persistent compile cache: ``JAX_COMPILATION_CACHE_DIR`` where
    set, else ``<checkout>/.jax_cache`` (a fixed path, so it hits)."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def _span():
    import jax
    return jax.profiler.TraceAnnotation


def judge(checks: dict, limits: dict, failed: int):
    """``(correct, [[name, value, limit]])``: every limited number at or
    under its limit, and no request failed."""
    rows = [["failed_requests", failed, 0]]
    for name, limit in limits.items():
        rows.append([name, checks.get(name, float("inf")), limit])
    correct = all(v is not None and v == v and v <= lim
                  for _, v, lim in rows)
    return correct, rows


def prepare(workload):
    """``(bench, cell entry, config, traffic, driver module)`` of a workload
    on this machine's chips, with the compile cache enabled; ``NoChip``
    where JAX finds no TPU or fewer chips than the cell asks for."""
    bench, entry, cfg, traffic = load_cell(workload)
    import jax
    if jax.default_backend() != "tpu":
        raise NoChip(f"JAX found no TPU (backend {jax.default_backend()!r})")
    if jax.device_count() < entry["chips"]:
        raise NoChip(f"the cell asks for {entry['chips']} chips, JAX found "
                     f"{jax.device_count()}")
    enable_cache()
    driver = importlib.import_module(
        f"benchmarks.chip.drivers.{traffic['kind']}")
    return bench, entry, cfg, traffic, driver


def run(workload, seed, seconds, trace):
    """One run; returns the result dict (also printed)."""
    bench, entry, cfg, traffic, driver = prepare(workload)
    import jax
    from benchmarks.chip import peaks as peaks_mod
    from benchmarks.chip import trace as trace_mod
    dev = jax.devices()[0]
    peaks = peaks_mod.peaks_for(dev.device_kind)

    cell = driver.setup(cfg, traffic, seed)
    setup_s = time.perf_counter() - T_START
    span = _span()
    trace_dir = ROOT / "chiprun_out" / "chipbench-trace" / workload
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    with span("bench.window"):
        win = driver.window(cell, seconds, span=span)
    if trace:
        jax.profiler.stop_trace()
    e2e, counters, attempted, failed = driver.results(cell, win)
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.devices())
    driver.release(cell, win)
    gc.collect()
    checks = driver.check(cell, win)
    correct, rows = judge(checks, cfg["limits"], failed)

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": jax.device_count(), "memory_peak_bytes": int(peak)}
    result = {"correct": bool(correct), "attempted": int(attempted),
              "failed": int(failed)}
    metrics = {}
    if trace:
        red = trace_mod.reduce(trace_mod.load_events(
            trace_mod.find_xplane(str(trace_dir))))
        ctx = types.SimpleNamespace(reduced=red, counters=counters, cfg=cfg,
                                    peaks=peaks, workload=workload)
        for m in cell_metrics(bench, workload, "per_layer"):
            v = load_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        result["metrics"] = metrics
        result["device"] = device
        result["breakdown"] = {"device_ops": red.device_ops,
                               "idle_gaps": red.idle_gaps}
    else:
        vals = dict(e2e, setup_s=setup_s)
        for m in cell_metrics(bench, workload, "end_to_end"):
            if m["name"] in vals:
                metrics[m["name"]] = {"value": float(vals[m["name"]]),
                                      "unit": m["unit"]}
        result["metrics"] = metrics
        result["device"] = device
    result["counters"] = {k: v for k, v in counters.items()
                          if not isinstance(v, list)}
    # a reading that cannot be had (no item, a crash of the comparison)
    # is infinite: written as the largest float JSON carries
    rows = [[n, v if v == v and abs(v) < 1e300 else 1e300, lim]
            for n, v, lim in rows]
    result["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in rows}
    for n, v, lim in rows:
        print(f"check {n} = {v!r} (limit {lim!r})", file=sys.stderr)
    print(f"correct = {correct}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    try:
        run(a.workload, a.seed, a.seconds, a.trace)
    except NoChip as e:
        print(f"chipbench: {e}; nothing was run", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
