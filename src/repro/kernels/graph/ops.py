"""Public entry points for the stage-2 graph engine.

The packed adjacency is *stored* at the kernels' padded shape
(``stored_shape``): rows rounded up to the row block, words to the column
block, of ``graph_blocks``.  ``init_stored_adj`` builds it that way once,
and every prune and CC hop then runs on it as it is: nothing graph-sized
is padded, copied or sliced per call.  All
padding is exact: padded adjacency bits are 0 (AND-monotone, never
re-set), padded column labels are ``BIG_LABEL`` (never the min), and
padded rows have no bits, so their outputs are never read.  Only the
O(n) vectors and labels are padded to the stored extents here; labels
come back at the caller's length.

Reference and pallas runs store the same shape (backend-independent, so
they carry bit-identical state).  A sharded runtime stores each shard's
rows at ``stored_shape(n_local, n)``, and ``user_rows`` reads the real
users' rows back in user order.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..pad import SUB, round_up
from .graph import cc_hop_packed_pallas, prune_packed_pallas
from .ref import (BIG_LABEL, cc_hop_packed_ref, init_packed_adj, pack_bits,
                  packed_words, pad_rows, prune_packed_ref, unpack_bits)

__all__ = [
    "BIG_LABEL", "init_packed_adj", "pack_bits", "packed_words",
    "unpack_bits", "prune_packed", "cc_hop_packed", "graph_blocks",
    "stored_shape", "init_stored_adj", "user_rows",
]


BLOCK_I = 256          # kernel row tile
BLOCK_J = 4096         # kernel column tile, in bits (128 u32 words)


def graph_blocks(n_rows: int, n_cols: int) -> tuple[int, int, int, int]:
    """(rows_pad, cols_pad, bi, bj) the tiled kernels run at.

    Blocks clamp to the (sublane/word-aligned) problem size so small graphs
    run a single tile; at scale they give a ``[256, 128]`` u32 packed tile
    — exactly lane width.
    """
    bi = min(BLOCK_I, round_up(n_rows, SUB))
    bj = min(BLOCK_J, round_up(n_cols, 32))
    return round_up(n_rows, bi), round_up(n_cols, bj), bi, bj


def stored_shape(n_rows: int, n_cols: int) -> tuple[int, int]:
    """``(rows, words)`` the packed graph is stored at: the extents the
    kernels run at."""
    rows_pad, cols_pad, _, _ = graph_blocks(n_rows, n_cols)
    return rows_pad, cols_pad // 32


def init_stored_adj(n: int, shards: int = 1) -> jnp.ndarray:
    """The fully-connected graph of ``n`` users at its stored shape.  With
    ``shards`` row shards (``n % shards == 0``), shard ``s`` holds users
    ``[s n_local, (s+1) n_local)`` in a block of ``stored_shape(n_local,
    n)``, and the blocks are stacked, so a row-sharded device_put hands
    every shard its own aligned block."""
    n_local = n // shards
    rows, words = stored_shape(n_local, n)
    blocks = [init_packed_adj(n_local, n, n_words=words,
                              row_offset=s * n_local, rows_pad=rows)
              for s in range(shards)]
    return blocks[0] if shards == 1 else jnp.concatenate(blocks)


def user_rows(adj: jnp.ndarray, n: int, shards: int = 1) -> jnp.ndarray:
    """The real users' rows of a stored graph, in user order, at the
    logical ``[n, ceil(n/32)]`` shape."""
    n_local = n // shards
    blocks = adj.reshape(shards, adj.shape[0] // shards, adj.shape[1])
    return blocks[:, :n_local, :packed_words(n)].reshape(n, -1)


def _blocks(packed):
    """``(bi, bj)`` of a graph at its stored shape."""
    R, W = packed.shape
    rows_pad, cols_pad, bi, bj = graph_blocks(R, W * 32)
    if (rows_pad, cols_pad) != (R, W * 32):
        raise ValueError(
            f"packed graph {packed.shape} is not at a stored shape; the "
            f"kernels run at {(rows_pad, cols_pad // 32)} (build it with "
            "init_stored_adj)")
    return bi, bj


def prune_packed(
    packed: jnp.ndarray,   # [R, W] uint32, as stored
    v_i: jnp.ndarray,      # [r, d], r <= R real rows
    cb_i: jnp.ndarray,     # [r] f32 confidence widths
    v_j: jnp.ndarray,      # [C, d], C <= W*32
    cb_j: jnp.ndarray,     # [C] f32
    gamma: float,
    *,
    use_pallas: bool | None = None,
    interpret: bool | None = None,
    row_block: int = 256,
) -> jnp.ndarray:
    """packed & (dist(v_i, v_j) < gamma (cb_i + cb_j)) — tiled on TPU.
    Returns the graph at ``packed``'s shape, which the Pallas path needs
    to be a stored shape."""
    R, W = packed.shape
    v_i = pad_rows(v_i.astype(jnp.float32), R)
    cb_i = pad_rows(cb_i.astype(jnp.float32), R)
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    if not use_pallas:
        return prune_packed_ref(packed, v_i, cb_i, v_j, cb_j, gamma,
                                row_block=row_block)

    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    d = v_j.shape[1]
    bi, bj = _blocks(packed)
    dp = round_up(d, SUB)

    def padv(v, n):
        out = pad_rows(v.astype(jnp.float32), n)
        if dp != d:
            out = jnp.pad(out, ((0, 0), (0, dp - d)))
        return out

    return prune_packed_pallas(
        packed, padv(v_i, R), cb_i,
        padv(v_j, W * 32), pad_rows(cb_j.astype(jnp.float32), W * 32),
        gamma, block_i=bi, block_j=bj, interpret=interpret,
    )


def cc_hop_packed(
    packed: jnp.ndarray,        # [R, W] uint32, as stored
    labels_self: jnp.ndarray,   # [r] i32, r <= R real rows
    labels_j: jnp.ndarray,      # [C] i32, C <= W*32
    *,
    use_pallas: bool | None = None,
    interpret: bool | None = None,
    row_block: int = 256,
) -> jnp.ndarray:
    """min(labels_self, neighbour-min of labels_j over set bits) — [r] i32.
    The Pallas path needs ``packed`` at a stored shape."""
    R, W = packed.shape
    r = labels_self.shape[0]
    labels_self = pad_rows(labels_self.astype(jnp.int32), R,
                           fill=BIG_LABEL)
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    if not use_pallas:
        return cc_hop_packed_ref(packed, labels_self, labels_j,
                                 row_block=row_block)[:r]

    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    bi, bj = _blocks(packed)
    out = cc_hop_packed_pallas(
        packed, labels_self,
        pad_rows(labels_j.astype(jnp.int32), W * 32, fill=BIG_LABEL),
        block_i=bi, block_j=bj, interpret=interpret,
    )
    return out[:r]
