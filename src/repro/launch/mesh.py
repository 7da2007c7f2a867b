"""Production meshes + spec-resolution helpers.

``make_production_mesh`` is a FUNCTION (not module state) so importing
this module never touches jax device state — the dry-run entry point sets
XLA_FLAGS before any jax import and only then builds meshes.

Mesh shapes (TPU v5e pods):
    single pod : (16, 16)     axes ("data", "model")   = 256 chips
    multi pod  : (2, 16, 16)  axes ("pod", "data", "model") = 512 chips

Model-family sharding conventions (DESIGN.md §6): PartitionSpecs in the
model code name the logical axes "data" / "model"; batch-like dims shard
over ("pod", "data") on the multi-pod mesh via ``batch_axes``.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P


def make_mesh(shape, axes, *, devices=None) -> Mesh:
    """THE mesh constructor of this repository: every axis ``Auto``.

    ``jax.make_mesh`` makes ``Explicit`` axes by default, under which a
    scatter into an item-sharded array (``core.catalog``'s staging) must
    name its ``out_sharding``.  The programs here place state with
    ``NamedSharding`` and shard explicitly with ``jax.shard_map``, so the
    compiler propagates everything else."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def batch_axes(mesh: Mesh):
    """Axes a batch/user dim shards over (pure DP across pods)."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def all_axes(mesh: Mesh):
    return tuple(mesh.axis_names)


def resolve(mesh: Mesh, spec: P) -> NamedSharding:
    """Map a logical PartitionSpec onto this mesh.

    Rule: the logical "data" entry becomes ("pod", "data") on a multi-pod
    mesh when it shards a *batch-like* dim; weight specs keep plain "data"
    (ZeRO sharding stays intra-pod: cross-pod is pure DP so gradients
    all-reduce over "pod" but weights are not gathered across pods).
    """
    return NamedSharding(mesh, spec)


def batch_spec(mesh: Mesh, rank: int, sharded_dim: int = 0) -> P:
    entries = [None] * rank
    entries[sharded_dim] = batch_axes(mesh)
    return P(*entries)

