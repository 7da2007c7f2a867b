"""Pure-jnp oracle for the stage-2 graph engine — bit-packed adjacency.

Layout (single source of truth for every consumer): adjacency row ``i`` is
``W = ceil(n_cols / 32)`` uint32 words, LSB-first within a word, so

    edge (i, j)  <->  bit ``j % 32`` of ``packed[i, j // 32]``.

Bits at columns ``>= n_cols`` are always 0 (no edge) — pruning only ever
ANDs bits away, so the zero padding is an invariant, not a convention.

The reference prune / CC-hop below are *row-blocked* (``lax.map`` over row
tiles): numerically identical to the one-shot dense math — the only
contracted axis is the feature dim ``d``, so tiling over (i, j) cannot
change any per-element contraction order — but peak memory is
``O(row_block * n_cols)`` instead of ``O(n^2)``.  That is what lets the
n=65536 graph bench run on a CPU host where the dense ``[n, n]`` f32
distance matrix (17 GB) cannot be materialized alongside the rest of the
run.  These are the ``REPRO_BACKEND=reference`` execution path and the
numerical oracle for the Pallas kernels in ``graph.py``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..pad import round_up

# Label sentinel for "no neighbour in this word": larger than any user id
# (labels live in user-id space) yet far from int32 overflow under min().
# A plain int so Pallas kernels can use it without capturing an array.
BIG_LABEL = 2**30


def packed_words(n_cols: int) -> int:
    """Number of uint32 words per adjacency row."""
    return (n_cols + 31) // 32


def pack_bits(dense: jnp.ndarray, n_words: int | None = None) -> jnp.ndarray:
    """[..., C] bool -> [..., W] uint32 (LSB-first; W >= ceil(C/32))."""
    C = dense.shape[-1]
    W = packed_words(C) if n_words is None else n_words
    pad = W * 32 - C
    if pad:
        dense = jnp.pad(dense, [(0, 0)] * (dense.ndim - 1) + [(0, pad)])
    r = dense.reshape(*dense.shape[:-1], W, 32).astype(jnp.uint32)
    shifts = jnp.arange(32, dtype=jnp.uint32)
    # each bit position contributes a distinct power of two, so sum == OR
    return jnp.sum(r << shifts, axis=-1, dtype=jnp.uint32)


def unpack_bits(packed: jnp.ndarray, n_cols: int) -> jnp.ndarray:
    """[..., W] uint32 -> [..., n_cols] bool (inverse of ``pack_bits``)."""
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = (packed[..., :, None] >> shifts) & jnp.uint32(1)
    flat = bits.reshape(*packed.shape[:-1], packed.shape[-1] * 32)
    return flat[..., :n_cols].astype(bool)


def init_packed_adj(n_rows: int, n_cols: int, n_words: int | None = None,
                    row_offset: int = 0,
                    rows_pad: int | None = None) -> jnp.ndarray:
    """Fully-connected packed adjacency minus self edges, [n_rows, W] u32
    (``[rows_pad, W]`` with ``rows_pad``: the rows past ``n_rows`` are 0).

    Built arithmetically (no [n, n] bool intermediate): full words below
    ``n_cols`` are 0xFFFFFFFF, the boundary word keeps its low
    ``n_cols % 32`` bits, and row ``i`` clears bit ``row_offset + i`` (its
    own column in the sharded row layout).
    """
    W = packed_words(n_cols) if n_words is None else n_words
    wi = jnp.arange(W, dtype=jnp.int32)
    rem = jnp.clip(n_cols - wi * 32, 0, 32)
    full = jnp.uint32(0xFFFFFFFF)
    partial = (jnp.uint32(1) << jnp.minimum(rem, 31).astype(jnp.uint32)
               ) - jnp.uint32(1)
    word = jnp.where(rem >= 32, full, partial)
    if rows_pad is None or rows_pad == n_rows:
        adj = jnp.broadcast_to(word, (n_rows, W))
    else:
        real = jnp.arange(rows_pad, dtype=jnp.int32)[:, None] < n_rows
        adj = jnp.where(real, word[None, :], jnp.uint32(0))
    i = jnp.arange(n_rows, dtype=jnp.int32) + row_offset
    dw, db = i // 32, (i % 32).astype(jnp.uint32)
    rows = jnp.arange(n_rows)
    return adj.at[rows, dw].set(adj[rows, dw] & ~(jnp.uint32(1) << db))


def pad_rows(a: jnp.ndarray, n_pad: int, fill=0) -> jnp.ndarray:
    """Pad the leading axis to ``n_pad`` with ``fill`` (no-op if aligned)."""
    if a.shape[0] == n_pad:
        return a
    pad = [(0, n_pad - a.shape[0])] + [(0, 0)] * (a.ndim - 1)
    return jnp.pad(a, pad, constant_values=fill)


def prune_packed_ref(
    packed: jnp.ndarray,   # [R, W] uint32
    v_i: jnp.ndarray,      # [R, d] row-side user vectors
    cb_i: jnp.ndarray,     # [R] f32 confidence widths (cb_width(occ_i))
    v_j: jnp.ndarray,      # [C, d] column-side user vectors (C <= W*32)
    cb_j: jnp.ndarray,     # [C] f32
    gamma: float,
    *,
    row_block: int = 256,
) -> jnp.ndarray:
    """AND the CLUB keep-mask ``dist < gamma (cb_i + cb_j)`` into ``packed``.

    Row-blocked: each ``lax.map`` step computes a ``[rb, W*32]`` distance
    slab, packs it, and ANDs — the full distance matrix never exists.
    Padded columns (bits >= C) compare against zero vectors but their
    adjacency bits are 0, so the AND keeps them 0.
    """
    R, W = packed.shape
    C = W * 32
    d = v_i.shape[1]
    v_j = pad_rows(v_j.astype(jnp.float32), C)
    cb_j = pad_rows(cb_j.astype(jnp.float32), C)
    sq_j = jnp.sum(v_j * v_j, axis=-1)

    rb = min(row_block, R)
    Rp = round_up(R, rb)
    packed_p = pad_rows(packed, Rp)
    v_p = pad_rows(v_i.astype(jnp.float32), Rp)
    cb_p = pad_rows(cb_i.astype(jnp.float32), Rp)

    def blk(args):
        p, vb, cbb = args
        d2 = (jnp.sum(vb * vb, axis=-1)[:, None] + sq_j[None, :]
              - 2.0 * vb @ v_j.T)
        dist = jnp.sqrt(jnp.maximum(d2, 0.0))
        keep = dist < gamma * (cbb[:, None] + cb_j[None, :])
        return p & pack_bits(keep, W)

    out = jax.lax.map(blk, (packed_p.reshape(-1, rb, W),
                            v_p.reshape(-1, rb, d),
                            cb_p.reshape(-1, rb)))
    return out.reshape(Rp, W)[:R]


def cc_hop_packed_ref(
    packed: jnp.ndarray,        # [R, W] uint32
    labels_self: jnp.ndarray,   # [R] i32 current labels of the rows
    labels_j: jnp.ndarray,      # [C] i32 current labels of the columns
    *,
    row_block: int = 256,
) -> jnp.ndarray:
    """One min-label hop: ``min(labels_self, min over set bits of labels_j)``.

    The pointer-doubling shortcut (``l[l]``) stays with the caller — it is
    an O(n) gather on the label vector, not a graph sweep.
    """
    R, W = packed.shape
    C = W * 32
    lj = pad_rows(labels_j.astype(jnp.int32), C, fill=BIG_LABEL)

    rb = min(row_block, R)
    Rp = round_up(R, rb)
    packed_p = pad_rows(packed, Rp)
    ls_p = pad_rows(labels_self.astype(jnp.int32), Rp, fill=BIG_LABEL)

    def blk(args):
        p, ls = args
        bits = unpack_bits(p, C)
        neigh = jnp.where(bits, lj[None, :], BIG_LABEL)
        return jnp.minimum(ls, jnp.min(neigh, axis=1))

    out = jax.lax.map(blk, (packed_p.reshape(-1, rb, W),
                            ls_p.reshape(-1, rb)))
    return out.reshape(Rp)[:R]
