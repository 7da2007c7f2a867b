"""Tiled Pallas kernels for the stage-2 graph engine.

Two kernels over the bit-packed adjacency (layout in ``ref.py``):

``prune``  grid (R/Bi, C/Bj).  Each step streams a ``[Bi, d] x [Bj, d]``
           pair of user-vector tiles into VMEM, forms the ``[Bi, Bj]``
           pairwise-distance tile and the CLUB threshold on the VPU/MXU,
           packs the keep-mask to ``[Bi, Bj/32]`` uint32 in registers
           (shift + sum — every bit is a distinct power of two, so sum is
           OR) and ANDs it into the adjacency tile.  The ``[n, n]`` f32
           distance matrix never reaches HBM: HBM traffic is the packed
           adjacency (n^2/8 bytes read + write) plus the streamed vector
           tiles, vs ``8 n^2 + 2 n^2`` bytes for the dense op-level path.

``cc_hop`` grid (R/Bi, C/Bj), output revisited across j.  Each step
           unpacks an adjacency tile via shift/mask in registers, takes
           the neighbour-min of the column labels, and folds it into the
           per-row running min (initialized with the row's own label at
           j == 0).  One pointer-doubling hop therefore reads n^2/8 bytes
           of adjacency instead of n^2 bool, plus O(n) label vectors.
           The label-chase ``min(l, l[l])`` stays outside (an O(n) gather).

TPU layout.  A lane cannot be split into ``[words, 32]`` in registers, so
the wrapper permutes each column tile of ``Bj`` columns (``Wb = Bj / 32``
words) into bit-major order: tile-local column ``b Wb + w`` holds the
original column ``32 w + b``.  Lane slice ``[b Wb, (b+1) Wb)`` then holds
bit ``b`` of every word of the tile, packing is 32 aligned slices shifted
and OR-ed, and unpacking is the same in reverse — no reshape.  The
column-side vectors (``v_j``, its squared norms, ``cb_j``, ``labels_j``)
are permuted in XLA next to the call, an O(n d) copy against the O(n^2/8)
sweep; the packed adjacency itself keeps its layout.  The kernels see the
adjacency as int32 (bit-identical; Mosaic has no unsigned reductions),
per-row vectors as ``[R, 1]`` columns, per-column vectors as ``[1, C]``
rows, and ``gamma`` in SMEM.

Both kernels are shape-polymorphic over rows vs columns, so the sharded
runtime reuses them unchanged on ``[n_local, n]`` row shards inside
``shard_map``.  Defaults (Bi=256, Bj=4096) make the packed tile
``[256, 128]`` — exactly lane-width, so the bit slices are whole vregs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import BIG_LABEL


def _bit_major(a: jnp.ndarray, block_j: int) -> jnp.ndarray:
    """Permute the leading (column) axis of ``a`` so tile-local position
    ``b Wb + w`` holds column ``32 w + b`` of its ``block_j`` tile."""
    C = a.shape[0]
    wb = block_j // 32
    t = a.reshape((C // block_j, wb, 32) + a.shape[1:])
    return jnp.swapaxes(t, 1, 2).reshape(a.shape)


def _prune_kernel(vi_ref, vj_ref, ni_ref, nj_ref, cbi_ref, cbj_ref,
                  gamma_ref, adj_ref, out_ref):
    d2 = (ni_ref[...] + nj_ref[...]
          - 2.0 * jax.lax.dot_general(
              vi_ref[...], vj_ref[...],
              dimension_numbers=(((1,), (1,)), ((), ())),
              precision=jax.lax.Precision.HIGHEST,
              preferred_element_type=jnp.float32,
          ))                                              # [Bi, Bj]
    dist = jnp.sqrt(jnp.maximum(d2, 0.0))
    keep = dist < gamma_ref[0] * (cbi_ref[...] + cbj_ref[...])

    bi, wb = adj_ref.shape
    words = jnp.zeros((bi, wb), jnp.int32)
    for b in range(32):
        words = words | (keep[:, b * wb:(b + 1) * wb].astype(jnp.int32) << b)
    out_ref[...] = adj_ref[...] & words


@functools.partial(jax.jit,
                   static_argnames=("block_i", "block_j", "interpret"))
def prune_packed_pallas(
    packed: jnp.ndarray,   # [R, Wp] u32, R % block_i == 0, Wp*32 % block_j == 0
    v_i: jnp.ndarray,      # [R, d]
    cb_i: jnp.ndarray,     # [R] f32
    v_j: jnp.ndarray,      # [C, d], C == Wp*32
    cb_j: jnp.ndarray,     # [C] f32
    gamma: float,
    *,
    block_i: int = 256,
    block_j: int = 4096,
    interpret: bool = False,
) -> jnp.ndarray:
    R, Wp = packed.shape
    C, d = v_j.shape
    assert R % block_i == 0, (R, block_i)
    assert C == Wp * 32 and C % block_j == 0, (C, Wp, block_j)
    wb = block_j // 32
    col = lambda i, j: (0, j)
    out = pl.pallas_call(
        _prune_kernel,
        grid=(R // block_i, C // block_j),
        in_specs=[
            pl.BlockSpec((block_i, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_j, d), lambda i, j: (j, 0)),
            pl.BlockSpec((block_i, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, block_j), col),
            pl.BlockSpec((block_i, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, block_j), col),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((block_i, wb), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((block_i, wb), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((R, Wp), jnp.int32),
        interpret=interpret,
        name="graph_prune",
    )(v_i, _bit_major(v_j, block_j),
      jnp.sum(v_i * v_i, axis=-1).reshape(R, 1),
      _bit_major(jnp.sum(v_j * v_j, axis=-1), block_j).reshape(1, C),
      cb_i.reshape(R, 1), _bit_major(cb_j, block_j).reshape(1, C),
      jnp.asarray(gamma, jnp.float32).reshape(1),
      jax.lax.bitcast_convert_type(packed, jnp.int32))
    return jax.lax.bitcast_convert_type(out, jnp.uint32)


def _cc_hop_kernel(adj_ref, lself_ref, lj_ref, out_ref):
    j = pl.program_id(1)
    adj = adj_ref[...]                # [Bi, Wb] i32 (packed bits)
    bi, wb = adj.shape
    m = jnp.full((bi, wb), BIG_LABEL, jnp.int32)
    for b in range(32):
        bit = ((adj >> b) & 1) > 0
        # lane slice b of the bit-major labels: the columns of bit b
        lab = jnp.broadcast_to(lj_ref[:, b * wb:(b + 1) * wb], (bi, wb))
        m = jnp.minimum(m, jnp.where(bit, lab, BIG_LABEL))
    m = jnp.min(m, axis=1, keepdims=True)                 # [Bi, 1]

    @pl.when(j == 0)
    def _():
        out_ref[...] = jnp.minimum(lself_ref[...], m)

    @pl.when(j > 0)
    def _():
        out_ref[...] = jnp.minimum(out_ref[...], m)


@functools.partial(jax.jit,
                   static_argnames=("block_i", "block_j", "interpret"))
def cc_hop_packed_pallas(
    packed: jnp.ndarray,        # [R, Wp] u32, aligned as in prune
    labels_self: jnp.ndarray,   # [R] i32
    labels_j: jnp.ndarray,      # [C] i32, C == Wp*32 (padding = BIG_LABEL)
    *,
    block_i: int = 256,
    block_j: int = 4096,
    interpret: bool = False,
) -> jnp.ndarray:
    R, Wp = packed.shape
    C = labels_j.shape[0]
    assert R % block_i == 0, (R, block_i)
    assert C == Wp * 32 and C % block_j == 0, (C, Wp, block_j)
    wb = block_j // 32
    out = pl.pallas_call(
        _cc_hop_kernel,
        grid=(R // block_i, C // block_j),
        in_specs=[
            pl.BlockSpec((block_i, wb), lambda i, j: (i, j)),
            pl.BlockSpec((block_i, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, block_j), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_i, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, 1), jnp.int32),
        interpret=interpret,
        name="cc_hop",
    )(jax.lax.bitcast_convert_type(packed, jnp.int32),
      labels_self.reshape(R, 1),
      _bit_major(labels_j, block_j).reshape(1, C))
    return out[:, 0]
