"""Plain reference of DistCLUB's four-stage epochs (Mahadik et al. 2020,
Listing 3), single host, in ``jax.numpy``.  Imports nothing of the program.

Per epoch, from the run key ``k``: ``k1, k3 = split(k)``;

1. stage 1: ``max_rounds`` lockstep rounds (round ``j`` keyed by
   ``split(k1, max_rounds)[j]``, split again into context and reward keys);
   a user with ``j < u_rounds`` scores its candidates with its own
   ``(Minv b, Minv)``, takes the first UCB argmax, is rewarded, and folds
   the pick by Sherman-Morrison;
2. stage 2: keep edge ``(i, j)`` iff kept before and ``|v_i - v_j| <
   gamma (cb_i + cb_j)``; label each user with its component's smallest
   id; per cluster ``Mc = I + sum (inv(Minv) - I)``, ``bc = sum b``, and the
   snapshots ``inv(Mc)[label]``, ``bc[label]``, ``seen/size`` frozen;
3. stage 3: as stage 1 with ``j < c_rounds``, scoring with the user's own
   statistics iff ``occ >= beta * mean_occ`` of its cluster, else the
   cluster's;
4. stage 4: ``delta = trunc((occ - mean_occ) / 2)`` moves budget between
   ``u_rounds`` and ``c_rounds``, each clipped to ``[0, max_rounds]``.

UCB: ``x.w + alpha sqrt(x' M x) sqrt(log1p(occ))``.  Contractions run at f32
with ``passes`` as in ``reference/serve.py`` (6 exact, 3 the control).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .serve import _HI, _pack, _unpack, cb_width, components, contract


def _ucb_pick(w, M, ctx, occ, alpha, passes):
    est = contract("nkd,nd->nk", ctx, w, passes)
    t = contract("nij,nkj->nki", M, ctx, passes)
    quad = jnp.sum(t * ctx, axis=-1)
    s = est + alpha * jnp.sqrt(jnp.maximum(quad, 0.0)) * jnp.sqrt(
        jnp.log1p(occ.astype(jnp.float32)))[:, None]
    return jnp.argmax(s, axis=1).astype(jnp.int32)


def _fold(Minv, b, occ, x, r, mask, passes):
    m = mask.astype(jnp.float32)
    xm = x * m[:, None]
    Mx = contract("nij,nj->ni", Minv, xm, passes)
    den = 1.0 + jnp.sum(xm * Mx, axis=1)
    Minv = Minv - Mx[:, :, None] * Mx[:, None, :] / den[:, None, None]
    return Minv, b + (r * m)[:, None] * x, occ + mask.astype(jnp.int32)


def _rounds(env, hyper, key, Minv, b, occ, budget, score, passes):
    contexts_fn, rewards_fn = env

    def step(carry, inp):
        j, k = inp
        Minv, b, occ = carry
        k_ctx, k_rew = jax.random.split(k)
        ctx = contexts_fn(k_ctx, occ)
        w, M = score(Minv, b, occ)
        choice = _ucb_pick(w, M, ctx, occ, hyper["alpha"], passes)
        x = jnp.take_along_axis(ctx, choice[:, None, None], axis=1)[:, 0]
        realized = rewards_fn(k_rew, occ, ctx, choice)[0]
        mask = j < budget
        Minv, b, occ = _fold(Minv, b, occ, x, realized, mask, passes)
        return (Minv, b, occ), jnp.sum(realized * mask)

    steps = hyper["max_rounds"]
    keys = jax.random.split(key, steps)
    (Minv, b, occ), rew = jax.lax.scan(step, (Minv, b, occ),
                                       (jnp.arange(steps), keys))
    return Minv, b, occ, rew


def _prune(adj, v, occ, gamma, rows, passes):
    n = v.shape[0]
    sq = jnp.sum(v * v, axis=1)
    cb = cb_width(occ)
    C = adj.shape[1] * 32

    def blk(r0):
        vb = jax.lax.dynamic_slice_in_dim(v, r0, rows)
        d2 = (jax.lax.dynamic_slice_in_dim(sq, r0, rows)[:, None] + sq[None]
              - 2.0 * contract("id,jd->ij", vb, v, passes))
        keep = jnp.sqrt(jnp.maximum(d2, 0.0)) < gamma * (
            jax.lax.dynamic_slice_in_dim(cb, r0, rows)[:, None] + cb[None])
        return _pack(jnp.pad(keep, ((0, 0), (0, C - n))))

    keep = jax.lax.map(blk, jnp.arange(0, n, rows))
    return adj & keep.reshape(adj.shape)


def _components(adj, n, rows):
    def hop(labels):
        def blk(r0):
            nb = _unpack(jax.lax.dynamic_slice_in_dim(adj, r0, rows), n)
            return jnp.min(jnp.where(nb, labels[None], 2 ** 30), axis=1)
        m = jax.lax.map(blk, jnp.arange(0, n, rows)).reshape(n)
        new = jnp.minimum(labels, m)
        return jnp.minimum(new, new[new])

    def cond(c):
        return c[1]

    def body(c):
        labels, _ = c
        new = hop(labels)
        return new, jnp.any(new != labels)

    init = jnp.arange(n, dtype=jnp.int32)
    return jax.lax.while_loop(cond, body, (init, jnp.array(True)))[0]


class Replay(NamedTuple):
    """What a run of the reference leaves: the final per-user statistics,
    the reward of every round, the final graph and cluster table, and
    each epoch's stage-2 inputs (``s2_*``: the statistics the prune
    read)."""
    Minv: jax.Array        # [n, d, d]
    b: jax.Array           # [n, d]
    occ: jax.Array         # [n]
    reward: jax.Array      # [rounds]
    adj: jax.Array         # [n, W] uint32, after the last stage 2
    labels: jax.Array      # [n]
    size: jax.Array        # [n] members per label
    seen: jax.Array        # [n] summed occ per label
    s2_Minv: jax.Array     # [epochs, n, d, d]
    s2_b: jax.Array        # [epochs, n, d]
    s2_occ: jax.Array      # [epochs, n]


@functools.partial(jax.jit, static_argnames=("env", "hyper_t", "n_epochs",
                                             "d", "passes", "rows"))
def _run(key, env, hyper_t, n_epochs, d, passes, rows):
    hyper = dict(hyper_t)
    n = env[2]
    eye = jnp.eye(d, dtype=jnp.float32)
    Minv = jnp.broadcast_to(eye, (n, d, d))
    b = jnp.zeros((n, d), jnp.float32)
    occ = jnp.zeros((n,), jnp.int32)
    adj = init_adj(n, rows)
    u_rounds = jnp.full((n,), hyper["sigma"], jnp.int32)
    c_rounds = u_rounds
    fns = env[:2]

    def own(Minv, b, occ):
        return contract("nij,nj->ni", Minv, b, passes), Minv

    def epoch(carry, k):
        Minv, b, occ, adj, u_rounds, c_rounds = carry
        k1, k3 = jax.random.split(k)
        Minv, b, occ, r1 = _rounds(fns, hyper, k1, Minv, b, occ, u_rounds,
                                   own, passes)
        s2 = (Minv, b, occ)
        v = contract("nij,nj->ni", Minv, b, passes)
        adj = _prune(adj, v, occ, hyper["gamma"], rows, passes)
        labels = _components(adj, n, rows)
        M = jnp.linalg.inv(Minv)
        Mc = jax.ops.segment_sum(M - eye, labels, num_segments=n) + eye
        bc = jax.ops.segment_sum(b, labels, num_segments=n)
        size = jax.ops.segment_sum(jnp.ones_like(labels), labels,
                                   num_segments=n)
        seen = jax.ops.segment_sum(occ, labels, num_segments=n)
        uMcinv = jnp.linalg.inv(Mc)[labels]
        ubc = bc[labels]
        umean = seen[labels].astype(jnp.float32) / jnp.maximum(size[labels],
                                                               1)
        v_clu = contract("nij,nj->ni", uMcinv, ubc, passes)

        def clu(Minv, b, occ):
            use = occ.astype(jnp.float32) >= hyper["beta"] * umean
            v_own = contract("nij,nj->ni", Minv, b, passes)
            return (jnp.where(use[:, None], v_own, v_clu),
                    jnp.where(use[:, None, None], Minv, uMcinv))

        Minv, b, occ, r3 = _rounds(fns, hyper, k3, Minv, b, occ, c_rounds,
                                   clu, passes)
        delta = ((occ.astype(jnp.float32) - umean) / 2.0).astype(jnp.int32)
        u_rounds = jnp.clip(u_rounds + delta, 0, hyper["max_rounds"])
        c_rounds = jnp.clip(c_rounds - delta, 0, hyper["max_rounds"])
        return ((Minv, b, occ, adj, u_rounds, c_rounds),
                (jnp.concatenate([r1, r3]), labels, size, seen, s2))

    keys = jax.random.split(key, n_epochs)
    (Minv, b, occ, adj, _, _), (rew, labels, size, seen, s2) = (
        jax.lax.scan(epoch, (Minv, b, occ, adj, u_rounds, c_rounds), keys))
    return Replay(Minv, b, occ, rew.reshape(-1), adj, labels[-1],
                  size[-1], seen[-1], *s2)


def init_adj(n, rows):
    """The fully connected packed graph without self edges."""
    W = (n + 31) // 32
    col = jnp.arange(W * 32)
    full = (col[None, :] < n) & (col[None, :] != jnp.arange(n)[:, None])
    return jax.lax.map(lambda r0: _pack(jax.lax.dynamic_slice_in_dim(
        full, r0, rows)), jnp.arange(0, n, rows)).reshape(n, W)


def run(key, contexts_fn, rewards_fn, n_users, hyper, n_epochs, d,
        passes=6, rows=1024) -> Replay:
    """A run of ``n_epochs`` epochs from a fresh state.  ``hyper``: dict
    with alpha, beta, gamma, sigma, max_rounds."""
    env = (contexts_fn, rewards_fn, n_users)
    return _run(key, env, tuple(sorted(hyper.items())), n_epochs, d, passes,
                min(rows, n_users))


# ---------------------------------------------------------------------------
# stage 2 of a whole run, judged pair by pair
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("rows", "cap"))
def _flip_rows(r0, adj, v, cb, known, gamma, rows, cap):
    """Pairs in rows ``[r0, r0 + rows)`` whose bit in ``adj`` differs from
    the f32 prune of every epoch's stage-2 vectors ``v [E, n, d]`` ANDed
    into the fully connected graph, among pairs of ``known`` users."""
    n = v.shape[1]
    sq = jnp.sum(v * v, axis=-1)                                  # [E, n]
    vb = jax.lax.dynamic_slice_in_dim(v, r0, rows, axis=1)
    dot = jnp.einsum("eid,ejd->eij", vb, v, precision=_HI)
    d2 = (jax.lax.dynamic_slice_in_dim(sq, r0, rows, axis=1)[:, :, None]
          + sq[:, None, :] - 2.0 * dot)
    th = gamma * (jax.lax.dynamic_slice_in_dim(cb, r0, rows, axis=1)
                  [:, :, None] + cb[:, None, :])
    keep = jnp.all(jnp.sqrt(jnp.maximum(d2, 0.0)) < th, axis=0)
    i = r0 + jnp.arange(rows)
    keep &= i[:, None] != jnp.arange(n)[None, :]
    side = _unpack(jax.lax.dynamic_slice_in_dim(adj, r0, rows), n)
    kb = jax.lax.dynamic_slice_in_dim(known, r0, rows)
    diff = (keep != side) & kb[:, None] & known[None, :]
    ii, jj = jnp.nonzero(diff, size=cap, fill_value=-1)
    return jnp.sum(diff), jnp.where(ii >= 0, ii + r0, -1), jj, side[
        jnp.maximum(ii, 0), jnp.maximum(jj, 0)]


def prune_check(adj, s2_Minv, s2_b, s2_occ, gamma, known, cap=4096):
    """``(flips, margin)`` of a final packed graph against the prune of
    the stage-2 statistics it was built from (``s2_* [E, n, ...]``, one
    entry per epoch), over pairs of ``known`` users.

    Candidate pairs are those whose bit differs from an f32 prune; each is
    settled in float64.  ``flips`` counts the pairs whose bit differs from
    the float64 prune; ``margin`` is the largest, over those pairs, of the
    smallest over epochs of ``|d^2 - th^2| / (|v_i|^2 + |v_j|^2 + th^2)``:
    how far from its keep threshold a flipped pair lay when it was decided
    (0 when no pair flipped)."""
    n = adj.shape[0]
    rows = 512 if n % 512 == 0 else n
    v = jnp.einsum("enij,enj->eni", s2_Minv, s2_b, precision=_HI)
    cb = cb_width(s2_occ)
    known = jnp.asarray(known, bool)
    pi, pj, bit = [], [], []
    for r0 in range(0, n, rows):
        c, i, j, s = _flip_rows(r0, adj, v, cb, known, gamma, rows, cap)
        if int(c):
            i, j, s = np.asarray(i), np.asarray(j), np.asarray(s)
            ok = i >= 0
            pi.append(i[ok])
            pj.append(j[ok])
            bit.append(s[ok])
    if not pi:
        return 0, 0.0
    pi, pj = np.concatenate(pi)[:cap], np.concatenate(pj)[:cap]
    bit = np.concatenate(bit)[:cap]
    users = np.unique(np.concatenate([pi, pj]))
    at = {u: k for k, u in enumerate(users)}
    a = np.array([at[u] for u in pi])
    c = np.array([at[u] for u in pj])
    sel = jnp.asarray(users)
    M = np.asarray(s2_Minv[:, sel], np.float64)
    bb = np.asarray(s2_b[:, sel], np.float64)
    o = np.asarray(s2_occ[:, sel], np.float64)
    v64 = np.einsum("euij,euj->eui", M, bb)
    cb64 = np.sqrt((1.0 + np.log1p(o)) / (1.0 + o))
    d2 = np.sum((v64[:, a] - v64[:, c]) ** 2, axis=-1)            # [E, P]
    th2 = (gamma * (cb64[:, a] + cb64[:, c])) ** 2
    keep64 = np.all(d2 < th2, axis=0) & (pi != pj)
    flipped = keep64 != bit.astype(bool)
    if not flipped.any():
        return 0, 0.0
    scale = (np.sum(v64[:, a] ** 2, -1) + np.sum(v64[:, c] ** 2, -1) + th2)
    rel = np.min(np.abs(d2 - th2) / np.maximum(scale, 1e-30), axis=0)
    return int(flipped.sum()), float(rel[flipped].max())
