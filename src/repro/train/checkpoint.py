"""Fault-tolerant checkpointing: atomic, keep-K, mesh-independent.

Design (DESIGN.md §6):
  * State is saved as host numpy arrays keyed by flattened pytree paths
    (npz) plus a msgpack-free JSON manifest (step, keys, shapes, dtypes).
    No mesh/sharding info is persisted — restore re-shards onto whatever
    mesh the new job has (**elastic**: scale from 256 to 512 chips or down
    to 1 CPU between runs; the serving sessions round-trip through this).
  * Writes go to ``<dir>/tmp-<step>`` then ``os.replace`` into place —
    a crashed writer never corrupts the latest checkpoint (atomicity).
  * ``keep`` most-recent checkpoints are retained; ``latest_step`` scans
    the directory, so a restarted job just calls ``restore_latest``.
  * ``restore_latest`` is corruption-tolerant: a checkpoint that fails to
    load (truncated npz, malformed or wrong-magic manifest, missing keys
    — e.g. torn by a crash mid-copy on a non-atomic filesystem) is
    skipped with a warning and the next-newest good one is restored; it
    only raises if NO checkpoint in the directory loads.

This is deliberately dependency-free (no orbax in the container) but
API-compatible in spirit: save(state, step) / restore(step, like, mesh).
"""
from __future__ import annotations

import json
import os
import pathlib
import shutil
import warnings
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

_MAGIC = "repro-ckpt-v1"


def _flatten(tree) -> dict[str, Any]:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(path): leaf for path, leaf in flat}


class CheckpointManager:
    def __init__(self, directory: str | os.PathLike, keep: int = 3):
        self.dir = pathlib.Path(directory)
        self.dir.mkdir(parents=True, exist_ok=True)
        self.keep = keep

    # -- paths ------------------------------------------------------------
    def _step_dir(self, step: int) -> pathlib.Path:
        return self.dir / f"step-{step:010d}"

    def steps(self) -> list[int]:
        out = []
        for p in self.dir.glob("step-*"):
            if (p / "manifest.json").exists():
                out.append(int(p.name.split("-")[1]))
        return sorted(out)

    def latest_step(self) -> int | None:
        s = self.steps()
        return s[-1] if s else None

    # -- save -------------------------------------------------------------
    def save(self, state, step: int) -> pathlib.Path:
        flat = _flatten(state)
        host = {k: np.asarray(jax.device_get(v)) for k, v in flat.items()}
        tmp = self.dir / f"tmp-{step}"
        if tmp.exists():
            shutil.rmtree(tmp)
        tmp.mkdir(parents=True)
        # npz can't store ml_dtypes (bfloat16 &co) — persist their raw bits;
        # the manifest keeps the logical dtype for restore.
        np.savez(tmp / "arrays.npz", **{
            str(i): (v.view(np.uint16) if v.dtype.name == "bfloat16" else v)
            for i, v in enumerate(host.values())
        })
        manifest = {
            "magic": _MAGIC,
            "step": step,
            "keys": list(host.keys()),
            "shapes": [list(v.shape) for v in host.values()],
            "dtypes": [str(v.dtype) for v in host.values()],
        }
        (tmp / "manifest.json").write_text(json.dumps(manifest))
        final = self._step_dir(step)
        if final.exists():
            shutil.rmtree(final)
        os.replace(tmp, final)                      # atomic publish
        self._gc()
        return final

    def _gc(self):
        steps = self.steps()
        for s in steps[: max(0, len(steps) - self.keep)]:
            shutil.rmtree(self._step_dir(s), ignore_errors=True)

    # -- restore ----------------------------------------------------------
    def restore(self, step: int, like, shardings=None):
        """Rebuild ``like``-structured state; device_put with ``shardings``
        (a matching pytree or None for host arrays)."""
        import ml_dtypes

        d = self._step_dir(step)
        manifest = json.loads((d / "manifest.json").read_text())
        magic = manifest.get("magic", _MAGIC)   # pre-magic saves pass
        if magic != _MAGIC:
            raise ValueError(f"bad checkpoint magic {magic!r} in {d}")
        with np.load(d / "arrays.npz") as z:
            arrays = []
            for i, dt in enumerate(manifest["dtypes"]):
                a = z[str(i)]
                if dt == "bfloat16":
                    a = a.view(ml_dtypes.bfloat16)
                arrays.append(a)
        by_key = dict(zip(manifest["keys"], arrays))

        flat, treedef = jax.tree_util.tree_flatten_with_path(like)
        leaves = []
        for path, leaf in flat:
            key = jax.tree_util.keystr(path)
            if key not in by_key:
                raise KeyError(f"checkpoint missing {key}")
            a = by_key[key]
            want = np.dtype(jax.numpy.asarray(leaf).dtype
                            if not hasattr(leaf, "dtype") else leaf.dtype)
            if a.dtype != want:
                a = a.astype(want)
            leaves.append(a)
        state = jax.tree_util.tree_unflatten(treedef, leaves)
        if shardings is not None:
            state = jax.device_put(state, shardings)
        return state

    def restore_latest(self, like, shardings=None):
        """Restore the newest LOADABLE checkpoint, skipping corrupted or
        partial ones (truncated arrays, bad magic, missing keys) — a torn
        write must cost at most one snapshot of progress, never the whole
        directory.  Raises only when every candidate fails."""
        steps = self.steps()
        if not steps:
            return None, None
        errors = []
        for step in reversed(steps):
            try:
                return self.restore(step, like, shardings), step
            except Exception as e:  # corrupt entry: skip to next-newest
                errors.append((step, e))
                warnings.warn(
                    f"skipping corrupted checkpoint step {step}: {e!r}")
        raise RuntimeError(
            f"no loadable checkpoint in {self.dir}: "
            + "; ".join(f"step {s}: {e!r}" for s, e in errors))
