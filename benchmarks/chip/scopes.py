"""Device time by named scope, and JAX's compile events by phase, for a
traced run: what ``trace.py`` does not keep.

The program names its stages and round steps with ``jax.named_scope``
(``SCOPES``).  XLA keeps the name stack in each HLO instruction's
``op_name`` metadata, and the profiler exports it as the ``tf_op`` of
each device operation in the ``*.trace.json.gz`` it writes beside the
``*.xplane.pb`` (``jax.profiler.ProfileData`` does not show it).
``scope_path`` keeps, in order, the components of an ``op_name`` that are
scope names, and not a Pallas kernel's own name (``choose`` is both):

    "jit(_run)/epoch/while/body/closed_call/stage2/cluster_inverse/jit(inv)/..."
        -> "epoch/stage2/cluster_inverse"

An operation outside every scope (a copy XLA inserted, say) has the path
``""``.  Times come from the xplane, as in ``trace.py``, and self time is
its rule: an operation nested in another on the same line is subtracted
from its parent, so a ``while`` is charged only the time between its
children, and the paths' seconds add up to the busy time.

    ev = load(xplane_path)
    scope_s = reduce_scopes(ev)            # {path: seconds}, per device
    phases = compile_phases(ev, compile_events.events())

The compile events are the program's own log (``repro.launch.
compile_events``), each stamped with the wall-clock time it ended; the
profile's ``Task Environment`` plane gives the wall-clock time at which
the trace began, which places the host span ``bench.window`` on that
clock.  Events that ended before the window are set-up, those inside it
the window's.

``of(ctx)`` is what the metric readers call: the reductions of the
run's trace (``TRACE_ROOT/<workload>``, where ``run.py`` writes it), made
once per process; it prints the ten largest scope paths and the compile phases to
standard error.  Where the program names no scope (every path is
``""``) or keeps no compile log, the readers have nothing to read.
"""
from __future__ import annotations

import dataclasses
import gzip
import json
import os
import pathlib
import sys

from . import trace

SCOPES = ("init", "epoch", "stage1", "stage2", "stage3", "stage4",
          "env_contexts", "env_rewards", "score", "choose", "fold",
          "round_metrics", "prune", "cc", "gram_inverse", "cluster_reduce",
          "cluster_inverse", "refresh_gram")
ENV_PLANE = "Task Environment"
TRACE_ROOT = (pathlib.Path(__file__).resolve().parents[2] / "chiprun_out"
              / "chipbench-trace")
# compile event names, as repro.launch.compile_events logs them
TRACE_EV = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EV = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE_EV = "/jax/core/compile/backend_compile_duration"
CACHE_READ_EV = "/jax/compilation_cache/cache_retrieval_time_sec"

_SCOPE_SET = frozenset(SCOPES)


def scope_path(op_name: str | None) -> str:
    """The scope components of an ``op_name``, in order, joined by ``/``.
    A named ``pallas_call`` puts its kernel's name on the stack just
    before ``pallas_call``; that is no scope, whatever it is called."""
    parts = (op_name or "").split("/")
    return "/".join(p for p, nxt in zip(parts, parts[1:] + [""])
                    if p in _SCOPE_SET and nxt != "pallas_call")


def innermost(path: str) -> str:
    return path.rsplit("/", 1)[-1]


def op_names(json_path: str) -> dict:
    """``{instruction name: op_name}`` of the device operations in the
    profiler's trace export (empty where there is none)."""
    if not os.path.exists(json_path):
        return {}
    with gzip.open(json_path, "rt") as f:
        events = json.load(f)["traceEvents"]
    devices = {e["pid"] for e in events
               if e.get("ph") == "M" and e.get("name") == "process_name"
               and e["args"]["name"].startswith("/device:TPU:")}
    return {e["name"]: e["args"]["tf_op"].rstrip(":") for e in events
            if e.get("pid") in devices and "tf_op" in e.get("args", {})}


def load(xplane_path: str) -> dict:
    """``trace.load_events``'s events with each device operation's
    ``op_name`` as a fourth field, and ``start_ns``: the wall-clock time at
    which the trace began (None where the profile does not say)."""
    import jax
    names = op_names(xplane_path.replace(".xplane.pb", ".trace.json.gz"))
    pd = jax.profiler.ProfileData.from_file(xplane_path)
    devices, host, start_ns = {}, [], None
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.extend([float(e.start_ns), float(e.duration_ns),
                                e.name, names.get(trace.op_label(e.name), "")]
                               for e in line.events)
            devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([float(e.start_ns), float(e.duration_ns), e.name]
                            for e in line.events
                            if e.name.startswith(trace.SPAN_PREFIX))
        elif plane.name == ENV_PLANE:
            start_ns = dict(plane.stats).get("profile_start_time")
    return {"devices": devices, "host": host, "start_ns": start_ns}


def window_bounds(events: dict):
    """``(lo, hi)`` on the trace's clock: the host span ``bench.window``,
    else the extent of the device operations (as ``trace.reduce``)."""
    win = [(s, s + d) for s, d, n in events.get("host", [])
           if n == trace.WINDOW_SPAN]
    if win:
        return min(a for a, _ in win), max(b for _, b in win)
    ops = [o for v in events["devices"].values() for o in v]
    return min(o[0] for o in ops), max(o[0] + o[1] for o in ops)


def reduce_scopes(events: dict) -> dict:
    """``{scope path: seconds}``: the self time of the device operations
    that started inside the window, by scope path, averaged over the
    devices.  An event without an ``op_name`` (a three-field event) falls
    under ``""``."""
    devices = {k: v for k, v in events["devices"].items() if v}
    if not devices:
        raise ValueError("the trace holds no device operation")
    lo, hi = window_bounds(events)
    out = {}
    for ops in devices.values():
        inside = [(o[0], o[1], scope_path(o[3] if len(o) > 3 else ""))
                  for o in ops if lo <= o[0] < hi]
        # a scope path has no " = ", so trace's op_label leaves it whole
        for path, t in trace._self_times(inside).items():
            out[path] = out.get(path, 0.0) + t
    return {k: v * 1e-9 / len(devices) for k, v in out.items()}


def _union_s(intervals) -> float:
    return sum(b - a for a, b in trace._union(intervals)) * 1e-9


def compile_phases(events: dict, log) -> dict | None:
    """JAX's compile events split at the window: ``{"setup": {...},
    "window": {...}, "after": {...}}``, each with the number of events
    of each kind (``traces``, ``lowerings``, ``compiles``,
    ``cache_reads``), ``trace_s`` (wall seconds inside a jaxpr trace or an
    MLIR lowering, nested traces counted once), ``compile_s`` (inside a
    backend compile, which holds any persistent-cache read) and
    ``cache_read_s``.  None without a wall-clock start or a log."""
    if events.get("start_ns") is None or log is None:
        return None
    lo, hi = window_bounds(events)
    lo_ns, hi_ns = events["start_ns"] + lo, events["start_ns"] + hi
    kinds = {TRACE_EV: "traces", LOWER_EV: "lowerings",
             COMPILE_EV: "compiles", CACHE_READ_EV: "cache_reads"}
    phases = {}
    for phase in ("setup", "window", "after"):
        phases[phase] = dict.fromkeys(kinds.values(), 0)
        phases[phase].update(trace_s=[], compile_s=[], cache_read_s=0.0)
    for event, secs, end_ns in log:
        if event not in kinds:
            continue
        phase = ("setup" if end_ns <= lo_ns else
                 "window" if end_ns <= hi_ns else "after")
        p = phases[phase]
        p[kinds[event]] += 1
        span = (end_ns - secs * 1e9, end_ns)
        if event in (TRACE_EV, LOWER_EV):
            p["trace_s"].append(span)
        elif event == COMPILE_EV:
            p["compile_s"].append(span)
        else:
            p["cache_read_s"] += secs
    for p in phases.values():
        p["trace_s"] = _union_s(p["trace_s"])
        p["compile_s"] = _union_s(p["compile_s"])
    return phases


def _compile_log():
    try:
        from repro.launch import compile_events
    except ImportError:            # a program that keeps no log
        return None
    return compile_events.events()


@dataclasses.dataclass
class Scoped:
    scope_s: dict                  # scope path -> seconds, per device
    phases: dict | None            # compile_phases, or None

    @property
    def named(self) -> bool:
        """Some operation fell under a scope."""
        return any(v > 0 for k, v in self.scope_s.items() if k)

    def inner_s(self, *names) -> float:
        """Seconds under paths whose innermost scope is one of ``names``."""
        return sum(v for k, v in self.scope_s.items()
                   if k and innermost(k) in names)

    def under_s(self, name) -> float:
        """Seconds under paths that hold the scope ``name``."""
        return sum(v for k, v in self.scope_s.items()
                   if name in k.split("/"))


_cache: dict = {}


def of(ctx) -> Scoped:
    """The run's reductions, made once per trace file."""
    path = trace.find_xplane(str(TRACE_ROOT / ctx.workload))
    if path not in _cache:
        ev = load(path)
        _cache[path] = Scoped(scope_s=reduce_scopes(ev),
                              phases=compile_phases(ev, _compile_log()))
        _report(_cache[path], ctx)
    return _cache[path]


def _report(scoped: Scoped, ctx) -> None:
    top = sorted(scoped.scope_s.items(), key=lambda kv: -kv[1])[:10]
    out = {"scopes": [[k, v] for k, v in top],
           "scopes_sum_s": sum(scoped.scope_s.values()),
           "busy_s": ctx.reduced.busy_s,
           "compile_phases": scoped.phases}
    print("scopes " + json.dumps(out), file=sys.stderr)
