"""Device time by the serving transaction's named scopes: ``scopes.py``'s
reduction over a wider set of scope names, for the serve cells.

The catalog transaction (``serve.step_catalog``) opens ``serve`` and,
under it, ``gather_score``, ``tile_bounds``, ``retrieve``, ``choose``,
``env_rewards``, ``fold`` and ``refresh``; the refresh holds stage 2's own
scopes (``stage2`` and its ``prune``, ``cc``, ...).  ``scopes.SCOPES``
names the DistCLUB epoch's; this module adds the serve names and reduces
the same trace by the same rule (``scopes.load``, ``trace._self_times``),
so the paths add up to busy.

    s = of(ctx)        # a scopes.Scoped over the serve scope paths

``of`` also prints, once per trace, the ten largest paths and the ten
operations with the most self time under ``refresh`` to standard error
(``serve_scopes {...}``): what a refresh spends its time on, copies and
pads included.  Where the program names no serve scope, every path is
``""`` and the readers have nothing to read.
"""
from __future__ import annotations

import json
import sys

from . import scopes, trace

SCOPES = scopes.SCOPES + ("serve", "gather_score", "tile_bounds",
                          "retrieve", "refresh")
_SCOPE_SET = frozenset(SCOPES)


def scope_path(op_name: str | None) -> str:
    """As ``scopes.scope_path``, over ``SCOPES``."""
    parts = (op_name or "").split("/")
    return "/".join(p for p, nxt in zip(parts, parts[1:] + [""])
                    if p in _SCOPE_SET and nxt != "pallas_call")


def reduce(events: dict) -> tuple[dict, list]:
    """``({scope path: seconds}, [[op, seconds]])``: self time by path in
    the window, averaged over the devices, and the ten operations with
    the most self time under ``refresh``."""
    devices = {k: v for k, v in events["devices"].items() if v}
    if not devices:
        raise ValueError("the trace holds no device operation")
    lo, hi = scopes.window_bounds(events)
    by_path, by_op = {}, {}
    for ops in devices.values():
        inside = [o for o in ops if lo <= o[0] < hi]
        paths = [scope_path(o[3] if len(o) > 3 else "") for o in inside]
        for path, t in trace._self_times(
                [(o[0], o[1], p) for o, p in zip(inside, paths)]).items():
            by_path[path] = by_path.get(path, 0.0) + t
        under = {trace.op_label(o[2]) for o, p in zip(inside, paths)
                 if "refresh" in p.split("/")}
        for label, t in trace._self_times(
                [(o[0], o[1], o[2]) for o in inside]).items():
            if label in under:
                by_op[label] = by_op.get(label, 0.0) + t
    n = len(devices)
    top = sorted(by_op.items(), key=lambda kv: -kv[1])[:10]
    return ({k: v * 1e-9 / n for k, v in by_path.items()},
            [[k, v * 1e-9 / n] for k, v in top])


_cache: dict = {}


def of(ctx) -> scopes.Scoped:
    """The run's reduction, made once per trace file."""
    path = trace.find_xplane(str(scopes.TRACE_ROOT / ctx.workload))
    if path not in _cache:
        scope_s, refresh_ops = reduce(scopes.load(path))
        _cache[path] = scopes.Scoped(scope_s=scope_s, phases=None)
        top = sorted(scope_s.items(), key=lambda kv: -kv[1])[:10]
        print("serve_scopes " + json.dumps(
            {"scopes": [[k, v] for k, v in top],
             "refresh_ops": refresh_ops,
             "busy_s": ctx.reduced.busy_s}), file=sys.stderr)
    return _cache[path]
