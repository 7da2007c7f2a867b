"""Reduce a JAX profiler trace to the numbers the per-layer metrics read.

    events = load_events(xplane_path)       # plain tuples, JSON-able
    red = reduce(events, kernels=KERNELS)

* busy time: the union of the intervals in which an operation ran on a
  device (the ``XLA Ops`` line of each ``/device:TPU:<i>`` plane),
  clipped to the traced window and averaged over the devices;
* kernel time: the summed device durations of the operations whose HLO
  instruction name is a Pallas kernel's ``name`` (a ``pallas_call`` named
  ``topk`` lowers to an instruction ``%topk.<n>``);
* the longest idle gaps of device 0, each tagged with the harness host
  span (``bench.*``, written with ``jax.profiler.TraceAnnotation``) that
  overlaps it most;
* the device operations that took most self time (an event nested in
  another on the same line is subtracted from its parent).

The window is the host span ``bench.window`` when the trace holds one,
else the extent of the device operations.  Device and host timestamps
share the trace's clock to within about a millisecond on a v5e host.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

KERNELS = ("topk", "topk_pruned", "choose", "rank1_update_inv",
           "rank1_update", "graph_prune", "cc_hop")
WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
_INSTR = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:\.\d+)?\s*=")


def op_base(name: str) -> str:
    """``'%topk_pruned.3 = (f32[...]) custom-call(...)'`` -> ``'topk_pruned'``."""
    m = _INSTR.match(name)
    return m.group(1) if m else name.split(" ")[0]


def op_label(name: str) -> str:
    """The instruction name with its suffix (``'fusion.12'``), for the
    breakdown: distinct fusions stay distinct."""
    head = name.split("=", 1)[0].strip()
    return head.lstrip("%") or name[:40]


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load_events(xplane_path: str) -> dict:
    """``{"devices": {plane: [[start_ns, dur_ns, name], ...]},
    "host": [[start_ns, dur_ns, name], ...]}`` — device operations of every
    TPU plane's ``XLA Ops`` line, and the harness's own host spans."""
    import jax
    pd = jax.profiler.ProfileData.from_file(xplane_path)
    devices, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.extend([float(e.start_ns), float(e.duration_ns),
                                e.name] for e in line.events)
            devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([float(e.start_ns), float(e.duration_ns), e.name]
                            for e in line.events
                            if e.name.startswith(SPAN_PREFIX))
    return {"devices": devices, "host": host}


def _union(intervals):
    """Merged, sorted ``[(a, b)]`` of possibly overlapping intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def _self_times(ops):
    """{label: self ns}: each op's duration minus the ops nested in it."""
    out = {}
    stack = []                      # [(end, label)]
    for start, dur, name in sorted(ops, key=lambda e: (e[0], -e[1])):
        end = start + dur
        while stack and stack[-1][0] <= start:
            stack.pop()
        label = op_label(name)
        out[label] = out.get(label, 0.0) + dur
        if stack:
            parent = stack[-1][1]
            out[parent] = out.get(parent, 0.0) - dur
        stack.append((end, label))
    return out


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float                  # averaged over devices
    kernel_s: dict                 # kernel name -> seconds, summed over devices / n
    n_devices: int
    device_ops: list               # [[label, seconds]] top by self time
    idle_gaps: list                # [[host span, seconds]] longest first


def reduce(events: dict, kernels=KERNELS, top: int = 10) -> Reduced:
    devices = {k: v for k, v in events["devices"].items() if v}
    if not devices:
        raise ValueError("the trace holds no device operation")
    host = events.get("host", [])
    win = [(s, s + d) for s, d, n in host if n == WINDOW_SPAN]
    if win:
        lo, hi = min(a for a, _ in win), max(b for _, b in win)
    else:
        lo = min(s for ops in devices.values() for s, _, _ in ops)
        hi = max(s + d for ops in devices.values() for s, d, _ in ops)
    n = len(devices)
    busy, kern, selft = 0.0, {}, {}
    first = sorted(devices)[0]
    gaps = []
    for plane, ops in devices.items():
        inside = [o for o in ops if lo <= o[0] < hi]
        merged = _clip(_union((s, s + d) for s, d, _ in inside), lo, hi)
        busy += sum(b - a for a, b in merged)
        for s, d, name in inside:
            base = op_base(name)
            if base in kernels:
                kern[base] = kern.get(base, 0.0) + d
        for label, t in _self_times(inside).items():
            selft[label] = selft.get(label, 0.0) + t
        if plane == first:
            edges = [lo] + [x for ab in merged for x in ab] + [hi]
            gaps = [(edges[i], edges[i + 1])
                    for i in range(0, len(edges) - 1, 2)
                    if edges[i + 1] > edges[i]]
    spans = [(s, s + d, name) for s, d, name in host if name != WINDOW_SPAN]

    def tag(a, b):
        best, over = "no-host-span", 0.0
        for s, e, name in spans:
            o = min(b, e) - max(a, s)
            if o > over:
                best, over = name, o
        return best

    gaps.sort(key=lambda g: g[0] - g[1])
    idle = [[tag(a, b), (b - a) * 1e-9] for a, b in gaps[:top]]
    ops_top = sorted(selft.items(), key=lambda kv: -kv[1])[:top]
    return Reduced(
        window_s=(hi - lo) * 1e-9,
        busy_s=busy * 1e-9 / n,
        kernel_s={k: v * 1e-9 / n for k, v in kern.items()},
        n_devices=n,
        device_ops=[[k, v * 1e-9 / n] for k, v in ops_top],
        idle_gaps=idle,
    )
