"""Plain reference of one catalog-serving transaction of DistCLUB, and of
the stage-2 refresh it may carry.  Imports nothing of the program.

Semantics (Mahadik et al. 2020, Listing 1-3, as a serving transaction):

* scoring statistics per request: ``v_own = Minv b``, ``v_clu = uMcinv ubc``;
  a user whose ``occ >= beta * umean_occ`` scores with its own ``(v, Minv)``,
  else with its cluster snapshot's;
* UCB of item ``x``: ``x.w + alpha sqrt(x' Minv x) sqrt(log1p(occ))``; the
  served item is the UCB argmax over the whole live catalog (the program
  shortlists the top ``k_short`` and picks the best of them: the same item);
* feedback: Sherman-Morrison on ``Minv`` and ``b += r x``, one request at a
  time in batch order (a user twice in a batch is folded twice);
* refresh: keep edge ``(i, j)`` iff it was kept before and
  ``|v_i - v_j| < gamma (cb_i + cb_j)``, ``cb = sqrt((1 + log1p occ) /
  (1 + occ))``; labels are the smallest user id of each connected component.

Contractions run on the device at f32 with ``passes=6`` (HIGHEST), or
with ``passes=3``, a bf16x3 product emulated from bf16 parts, which is the
precision one step below and serves as the control; disputed picks and
boundary pairs are then settled in float64 on the host.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_HI = jax.lax.Precision.HIGHEST


def _split(a):
    hi = a.astype(jnp.bfloat16).astype(jnp.float32)
    lo = (a - hi).astype(jnp.bfloat16).astype(jnp.float32)
    return hi, lo


def contract(spec, a, b, passes=6):
    """``einsum(spec, a, b)`` in f32: exact products (``passes=6``) or the
    three-pass bf16 product (hi*hi + hi*lo + lo*hi)."""
    if passes == 6:
        return jnp.einsum(spec, a, b, precision=_HI)
    ah, al = _split(a)
    bh, bl = _split(b)
    return (jnp.einsum(spec, ah, bh, precision=_HI)
            + jnp.einsum(spec, ah, bl, precision=_HI)
            + jnp.einsum(spec, al, bh, precision=_HI))


def mix(rows, beta, passes=6):
    """``(w, minv_eff)`` of request rows ``{Minv, b, occ, uMcinv, ubc,
    umean_occ}`` (device arrays)."""
    v_own = contract("nij,nj->ni", rows["Minv"], rows["b"], passes)
    v_clu = contract("nij,nj->ni", rows["uMcinv"], rows["ubc"], passes)
    own = rows["occ"].astype(jnp.float32) >= beta * rows["umean_occ"]
    w = jnp.where(own[:, None], v_own, v_clu)
    M = jnp.where(own[:, None, None], rows["Minv"], rows["uMcinv"])
    return w, M


@functools.partial(jax.jit, static_argnames=("k", "passes"))
def _tile_topk(w, Mf, widen, x, live, alpha, k, passes):
    d = x.shape[1]
    G = (x[:, :, None] * x[:, None, :]).reshape(x.shape[0], d * d)
    est = contract("nd,td->nt", w, x, passes)
    quad = contract("nq,tq->nt", Mf, G, passes)
    s = est + alpha * jnp.sqrt(jnp.maximum(quad, 0.0)) * widen[:, None]
    s = jnp.where(live[None, :] > 0, s, -jnp.inf)
    return jax.lax.top_k(s, k)


def ucb_top(w, M, occ, emb, live, alpha, k=8, tile=65536, passes=6):
    """Top-``k`` UCB items of every request over the whole catalog, by
    item tiles: ``(scores [B, k], ids [B, k])`` on the host."""
    B, d = w.shape
    Mf = M.reshape(B, d * d)
    widen = jnp.sqrt(jnp.log1p(occ.astype(jnp.float32)))
    best_s, best_i = [], []
    for t0 in range(0, emb.shape[0], tile):
        s, i = _tile_topk(w, Mf, widen, emb[t0:t0 + tile],
                          live[t0:t0 + tile], alpha, k, passes)
        best_s.append(np.asarray(s))
        best_i.append(np.asarray(i) + t0)
    s = np.concatenate(best_s, axis=1)
    i = np.concatenate(best_i, axis=1)
    top = np.argsort(-s, axis=1, kind="stable")[:, :k]
    return np.take_along_axis(s, top, 1), np.take_along_axis(i, top, 1)


def ucb64(w, M, occ, x, alpha):
    """float64 UCB of rows ``x [B, m, d]`` for requests ``(w, M, occ)``."""
    quad = np.einsum("bmd,bde,bme->bm", x, M, x)
    widen = np.sqrt(np.log1p(occ.astype(np.float64)))
    return (np.einsum("bmd,bd->bm", x, w)
            + alpha * np.sqrt(np.maximum(quad, 0.0)) * widen[:, None])


def mix64(rows, beta):
    r = {k: np.asarray(v, np.float64) for k, v in rows.items()}
    own = r["occ"] >= beta * r["umean_occ"]
    v_own = np.einsum("nij,nj->ni", r["Minv"], r["b"])
    v_clu = np.einsum("nij,nj->ni", r["uMcinv"], r["ubc"])
    w = np.where(own[:, None], v_own, v_clu)
    M = np.where(own[:, None, None], r["Minv"], r["uMcinv"])
    return w, M, r["occ"]


def item_gap(items, cand_ids, emb_rows, w64, M64, occ64, alpha, n_items):
    """Largest float64 UCB shortfall of a served item below the best of
    the candidates, relative to ``max(1, |best|)``; ``inf`` for a request
    that got no valid item.  ``emb_rows(ids)`` returns float64 rows."""
    ids = np.concatenate([cand_ids, items[:, None]], axis=1)
    ok = (items >= 0) & (items < n_items)
    x = emb_rows(np.clip(ids, 0, n_items - 1))
    s = ucb64(w64, M64, occ64, x, alpha)
    best = s[:, :-1].max(axis=1)
    gap = (best - s[:, -1]) / np.maximum(1.0, np.abs(best))
    gap = np.where(ok, np.maximum(gap, 0.0), np.inf)
    return float(gap.max()) if gap.size else 0.0


def fold64(Minv, b, occ, x, r):
    """Sequential Sherman-Morrison of one user's float64 rows."""
    for xi, ri in zip(x, r):
        Mx = Minv @ xi
        Minv = Minv - np.outer(Mx, Mx) / (1.0 + xi @ Mx)
        b = b + ri * xi
        occ = occ + 1
    return Minv, b, occ


def fold_err(uids, x, r, ambiguous, before, after):
    """Largest relative gap of the program's folded rows against the
    float64 fold of ``before`` (dicts of per-request rows, batch order).
    Users with an ambiguous click (draw within rounding of p) are left
    out; returns ``(err, users_compared, users_left_out)``; a wrong
    ``occ`` reads ``inf``."""
    err, seen, skipped = 0.0, 0, 0
    for u in np.unique(uids[uids >= 0]):
        at = np.nonzero(uids == u)[0]
        if ambiguous[at].any():
            skipped += 1
            continue
        i0 = at[0]
        M, bb, o = fold64(np.asarray(before["Minv"][i0], np.float64),
                          np.asarray(before["b"][i0], np.float64),
                          int(before["occ"][i0]), x[at], r[at])
        if int(after["occ"][i0]) != o:
            return np.inf, seen, skipped
        eM = np.abs(after["Minv"][i0] - M).max() / max(np.abs(M).max(), 1e-30)
        eb = np.abs(after["b"][i0] - bb).max() / max(np.abs(bb).max(), 1.0)
        err = max(err, float(eM), float(eb))
        seen += 1
    return err, seen, skipped


def fold_rows(Minv, b, occ, uids, x, r, passes=6):
    """The feedback fold on the device at ``passes``: per request rows in
    batch order (``Minv [B, d, d]``, ``b [B, d]``, ``occ [B]``); each
    user's result lands on its first position, folded once per occurrence
    in batch order."""
    uids = np.asarray(uids)
    first = {}
    rank = np.zeros(len(uids), np.int64)
    for i, u in enumerate(uids):
        if u < 0:
            rank[i] = -1
            continue
        rank[i] = first.setdefault(int(u), [i, 0])[1]
        first[int(u)][1] += 1
    home = np.array([first[int(u)][0] if u >= 0 else i
                     for i, u in enumerate(uids)])
    x = jnp.asarray(x, jnp.float32)
    r = jnp.asarray(r, jnp.float32)
    for k in range(int(rank.max()) + 1 if len(rank) else 0):
        pos = np.nonzero(rank == k)[0]
        h = jnp.asarray(home[pos])
        xp = x[jnp.asarray(pos)]
        M = Minv[h]
        Mx = contract("nij,nj->ni", M, xp, passes)
        den = 1.0 + jnp.sum(xp * Mx, axis=1)
        Minv = Minv.at[h].set(M - Mx[:, :, None] * Mx[:, None, :]
                              / den[:, None, None])
        b = b.at[h].add(r[jnp.asarray(pos)][:, None] * xp)
        occ = occ.at[h].add(1)
    return Minv, b, occ


# ---------------------------------------------------------------------------
# stage 2: edge prune and connected components over the packed graph
# ---------------------------------------------------------------------------


def cb_width(occ):
    o = occ.astype(jnp.float32)
    return jnp.sqrt((1.0 + jnp.log1p(o)) / (1.0 + o))


def _pack(bits):
    """[r, C] bool -> [r, C/32] uint32, column j at bit j % 32 of word j // 32."""
    r, C = bits.shape
    words = bits.reshape(r, C // 32, 32).astype(jnp.uint32)
    return jnp.sum(words << jnp.arange(32, dtype=jnp.uint32), axis=-1,
                   dtype=jnp.uint32)


def _unpack(words, n):
    bits = (words[..., None] >> jnp.arange(32, dtype=jnp.uint32)) & 1
    return bits.reshape(words.shape[0], -1)[:, :n].astype(bool)


@functools.partial(jax.jit, static_argnames=("cap", "passes"))
def _prune_block(v_blk, cb_blk, v, sq, cb, gamma, adj_before, adj_after,
                 cap, passes):
    n = v.shape[0]
    dot = contract("id,jd->ij", v_blk, v, passes)
    d2 = jnp.sum(v_blk * v_blk, axis=1)[:, None] + sq[None, :] - 2.0 * dot
    keep = jnp.sqrt(jnp.maximum(d2, 0.0)) < gamma * (cb_blk[:, None]
                                                     + cb[None, :])
    C = adj_before.shape[1] * 32
    keep = jnp.pad(keep, ((0, 0), (0, C - n)))
    diff = (adj_before & _pack(keep)) ^ adj_after
    flips = _unpack(diff, n)
    i, j = jnp.nonzero(flips, size=cap, fill_value=-1)
    return jnp.sum(flips), i, j


def prune_flips(Minv, b, occ, gamma, adj_before, adj_after, rows=512,
                cap=4096, passes=6):
    """Pairs whose kept/pruned bit differs between ``adj_after`` and the
    reference prune of ``adj_before``: ``(count, [(i, j)])`` (at most ``cap``
    pairs listed)."""
    v = contract("nij,nj->ni", Minv, b, passes)
    sq = jnp.sum(v * v, axis=1)
    cb = cb_width(occ)
    n = v.shape[0]
    count, pairs = 0, []
    for r0 in range(0, n, rows):
        c, i, j = _prune_block(v[r0:r0 + rows], cb[r0:r0 + rows], v, sq, cb,
                               gamma, adj_before[r0:r0 + rows],
                               adj_after[r0:r0 + rows], cap, passes)
        c = int(c)
        if c:
            i, j = np.asarray(i), np.asarray(j)
            keep = i >= 0
            pairs.extend(zip(i[keep] + r0, j[keep]))
            count += c
    return count, pairs[:cap]


def prune_margin(pairs, Minv, b, occ, gamma):
    """Largest float64 distance of a flipped pair from the keep threshold,
    ``|d^2 - th^2|`` over ``|v_i|^2 + |v_j|^2 + th^2`` (0 when none flipped)."""
    if not pairs:
        return 0.0
    idx = np.array(pairs, np.int64)
    users = np.unique(idx)
    pos = {u: k for k, u in enumerate(users)}
    Mi = np.asarray(Minv[users], np.float64)
    bi = np.asarray(b[users], np.float64)
    oi = np.asarray(occ[users], np.float64)
    v = np.einsum("nij,nj->ni", Mi, bi)
    cb = np.sqrt((1.0 + np.log1p(oi)) / (1.0 + oi))
    a = np.array([pos[u] for u in idx[:, 0]])
    c = np.array([pos[u] for u in idx[:, 1]])
    d2 = np.sum((v[a] - v[c]) ** 2, axis=1)
    th = gamma * (cb[a] + cb[c])
    scale = np.sum(v[a] ** 2, 1) + np.sum(v[c] ** 2, 1) + th ** 2
    return float(np.max(np.abs(d2 - th ** 2) / np.maximum(scale, 1e-30)))


@functools.partial(jax.jit, static_argnames=("n", "rows"))
def _hop(adj, labels, n, rows):
    def blk(r0):
        nb = _unpack(jax.lax.dynamic_slice_in_dim(adj, r0, rows), n)
        return jnp.min(jnp.where(nb, labels[None, :], 2 ** 30), axis=1)
    m = jax.lax.map(blk, jnp.arange(0, n, rows)).reshape(n)
    new = jnp.minimum(labels, m)
    new = jnp.minimum(new, new[new])
    return new, jnp.any(new != labels)


def components(adj, n, rows=512):
    """Smallest user id of each connected component of the packed graph."""
    labels = jnp.arange(n, dtype=jnp.int32)
    rows = min(rows, n)
    while True:
        labels, changed = _hop(adj, labels, n, rows)
        if not bool(changed):
            return np.asarray(labels)
