"""Shared benchmark plumbing.

Scaling note (recorded in EXPERIMENTS.md): the paper's Table 3 runs 80k-4M
interactions on 64 EC2 cores; this container is ONE CPU core, so each
dataset clone runs a proportionally reduced interaction budget at the
paper's user counts and feature dims.  All comparisons are at MATCHED
interaction counts across algorithms, so ratios (speedup, reward ratio,
comm volume) are the meaningful outputs, not absolute seconds.
"""
from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys
import time

import jax

ROOT = pathlib.Path(__file__).resolve().parents[1]
RESULTS = ROOT / "results"
RESULTS.mkdir(parents=True, exist_ok=True)


def timed(fn, *args, repeats: int = 1, **kw):
    """(wall seconds of best repeat, result). Blocks on jax async."""
    best = float("inf")
    out = None
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn(*args, **kw)
        jax.block_until_ready(out)
        best = min(best, time.perf_counter() - t0)
    return best, out


def emit(name: str, us_per_call: float, derived: str = ""):
    print(f"{name},{us_per_call:.1f},{derived}")


def save_json(name: str, payload):
    (RESULTS / f"{name}.json").write_text(json.dumps(payload, indent=1))


def run_cpu_child(code: str, n_devices: int, timeout: int = 900) -> str:
    """Run ``code`` in a child Python on ``n_devices`` virtual CPU devices
    and return its stdout.

    The child is pinned to the CPU (``JAX_PLATFORMS=cpu``): a parent that
    has touched JAX holds the accelerator, so a child that asked for it
    would fail or hang.  A child that fails raises — no error row is
    recorded in its place — so a broken multi-device path fails the
    bench.  Rows built from the output carry ``"platform": "cpu"``."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = str(ROOT / "src")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=timeout)
    if out.returncode != 0:
        raise RuntimeError(f"{n_devices}-device CPU child failed "
                           f"(exit {out.returncode}):\n"
                           + (out.stderr or out.stdout)[-2000:])
    return out.stdout
