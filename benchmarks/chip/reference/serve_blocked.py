"""The stage-2 part of ``serve.py``'s reference in exact row blocks, for a
user count that no block divides (MovieLens-25M's 162,541).  Imports
nothing of the program.

``serve.prune_flips`` and ``serve.components`` slice the packed graph in
fixed blocks of rows, so they need ``n`` to be a whole number of blocks
and the graph to hold exactly ``n`` rows.  Here every block is sliced to
the users it holds (the last one is shorter), so the graph may carry
padding rows and words past the real users, as the program stores it;
the padding is never read.  Same semantics, same float64 settlement of
boundary pairs (``serve.prune_margin`` is used as it is).  A block counts
its flipped pairs on the device and lists them on the host only when
there are any: ``jnp.nonzero`` over a ``[512, n]`` block is a
scatter-add of every element on a TPU, seconds a block at this size.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import serve as ref


def _vectors(Minv, b, occ, passes):
    v = ref.contract("nij,nj->ni", Minv.astype(jnp.float32), b, passes)
    return v, jnp.sum(v * v, axis=1), ref.cb_width(occ)


def _blocks(n, rows):
    return [(r0, min(r0 + rows, n)) for r0 in range(0, n, rows)]


@functools.partial(jax.jit, static_argnames=("passes",))
def _flip_words(v_blk, cb_blk, v, sq, cb, gamma, adj_blk, other_blk,
                passes):
    """``(count, words)``: the packed bits of a block where ``other_blk``
    differs from the reference prune of ``adj_blk``, and their number."""
    n = v.shape[0]
    dot = ref.contract("id,jd->ij", v_blk, v, passes)
    d2 = jnp.sum(v_blk * v_blk, axis=1)[:, None] + sq[None, :] - 2.0 * dot
    keep = jnp.sqrt(jnp.maximum(d2, 0.0)) < gamma * (cb_blk[:, None]
                                                     + cb[None, :])
    keep = jnp.pad(keep, ((0, 0), (0, adj_blk.shape[1] * 32 - n)))
    diff = (adj_blk & ref._pack(keep)) ^ other_blk
    return jnp.sum(jax.lax.population_count(diff)), diff


def _listed(count, pairs, c, diff, r0, n, cap):
    if c and len(pairs) < cap:
        words = np.asarray(diff)
        bits = (words[..., None] >> np.arange(32, dtype=np.uint32)) & 1
        i, j = np.nonzero(bits.reshape(words.shape[0], -1)[:, :n])
        pairs.extend(zip(i + r0, j))
    return count + c


def prune_flips(Minv, b, occ, gamma, adj_before, adj_after, rows=512,
                cap=4096, passes=6):
    """As ``serve.prune_flips``: pairs of real users whose bit in
    ``adj_after`` differs from the reference prune of ``adj_before``."""
    v, sq, cb = _vectors(Minv, b, occ, passes)
    n = v.shape[0]
    count, pairs = 0, []
    for r0, r1 in _blocks(n, rows):
        c, diff = _flip_words(v[r0:r1], cb[r0:r1], v, sq, cb, gamma,
                              adj_before[r0:r1], adj_after[r0:r1], passes)
        count = _listed(count, pairs, int(c), diff, r0, n, cap)
    return count, pairs[:cap]


def control_flips(Minv, b, occ, gamma, adj_before, rows=512, cap=4096):
    """The control's prune (contractions at three bf16 passes) against
    the reference's (exact f32 products), over the edges of
    ``adj_before``: ``(count, [(i, j)])`` as ``prune_flips``."""
    v, sq, cb = _vectors(Minv, b, occ, 6)
    v3, sq3, _ = _vectors(Minv, b, occ, 3)
    n = v.shape[0]
    count, pairs = 0, []
    for r0, r1 in _blocks(n, rows):
        blk = adj_before[r0:r1]
        _, exact = _flip_words(v[r0:r1], cb[r0:r1], v, sq, cb, gamma, blk,
                               jnp.zeros_like(blk), 6)
        c, diff = _flip_words(v3[r0:r1], cb[r0:r1], v3, sq3, cb, gamma, blk,
                              exact, 3)
        count = _listed(count, pairs, int(c), diff, r0, n, cap)
    return count, pairs[:cap]


@jax.jit
def _neighbour_min(adj_blk, labels):
    nb = ref._unpack(adj_blk, labels.shape[0])
    return jnp.min(jnp.where(nb, labels[None, :], 2 ** 30), axis=1)


def components(adj, n, rows=512):
    """Smallest user id of each connected component of the real users'
    graph (rows and columns ``< n``)."""
    labels = jnp.arange(n, dtype=jnp.int32)
    while True:
        m = jnp.concatenate([_neighbour_min(adj[r0:r1], labels)
                             for r0, r1 in _blocks(n, rows)])
        new = jnp.minimum(labels, m)
        new = jnp.minimum(new, new[new])
        if not bool(jnp.any(new != labels)):
            return np.asarray(new)
        labels = new
