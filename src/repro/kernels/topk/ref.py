"""Row-blocked jnp oracle for the streaming UCB top-K retrieval kernel.

Semantics (shared with the Pallas kernel via :func:`select_topk`):

    score[u, i] = x_i . w_u + alpha sqrt(x_i' Minv_u x_i) sqrt(log1p(occ_u))
    shortlist_u = the ``k_short`` items with the largest scores, ordered by
                  (score desc, item id asc); dead items (``live == 0``)
                  score -inf and can only fill an underfull shortlist.

This is the same UCB the fused choose kernel computes over a per-round
slate — retrieval is "choose" with the catalog as the slate — so a
two-stage recommend (shortlist -> choose) degenerates to the direct-slate
path when the catalog fits in one slate.

The oracle never materializes the ``[n, N_items]`` score matrix either:
users are processed in ``row_block`` groups via ``lax.map`` and items in
``item_block`` tiles via ``lax.scan``, carrying a running
``[row_block, k_short]`` shortlist — ``N_items = 2**20`` runs on one CPU
core in a few seconds (see ``benchmarks/bench_retrieval.py``).

Tiling invariance (load-bearing for every parity claim): each item's
score contracts only over the feature dim, so its bits do not depend on
the tile partition, and :func:`select_topk` selects by *value*
``(score, id)`` — therefore reference/pallas, any block sizes, and the
per-shard + merge path of the item-sharded catalog all produce the
identical shortlist.

The quadratic form is computed as ``vec(Minv) . vec(x x')`` — one
``[rows, d^2] x [d^2, tile]`` contraction — matching the Pallas kernel's
MXU formulation bit for bit in interpret mode.

Cluster-pruned variant (:func:`topk_ref_pruned`): the item stream is the
cluster-SORTED catalog (``core.itemclub`` permutes slots so each tile
holds one cluster's items) and every (user, tile) pair carries a
precomputed upper bound ``tb`` (:func:`tile_bounds`).  A tile is skipped
for a whole user row-block iff STRICTLY ``tb < floor`` for every user in
the block, where ``floor`` is each user's running k-th shortlist score —
any item in such a tile scores ``<= tb < floor``, i.e. strictly below k
items already found, so it cannot enter the final shortlist even under
(score, id) tie-breaks.  ``tb == floor`` must NOT skip (an equal-score
item with a smaller id could still displace the floor entry), which is
why the comparison is strict.  Because per-item score bits are
tile-partition-invariant and :func:`select_topk` folds by value, the
pruned shortlist is BIT-EQUAL to the unpruned one — ties, churn and all.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

INT_MAX = jnp.iinfo(jnp.int32).max
NEG_INF = -jnp.inf


def select_topk(buf_s, buf_i, k: int):
    """Top-``k`` of each row of ``(buf_s [n, W], buf_i [n, W])`` by
    (score desc, id asc) — repeated (max score, min id) selection, so the
    result depends only on the (score, id) value multiset, never on the
    buffer order.  Returns ``(scores [n, k], ids [n, k])`` sorted the
    same way.  Shared verbatim by the oracle and the Pallas kernel.

    ``buf_s``/``buf_i`` may also be tuples of ``[n, W_p]`` parts — the
    selection over the parts equals the selection over their
    concatenation (it is value-based), and the kernel passes its running
    shortlist and the new tile as two parts because Mosaic cannot
    concatenate at a lane offset that is not a multiple of 128."""
    parts_s = tuple(buf_s) if isinstance(buf_s, (tuple, list)) else (buf_s,)
    parts_i = tuple(buf_i) if isinstance(buf_i, (tuple, list)) else (buf_i,)
    n = parts_s[0].shape[0]
    cols = jax.lax.broadcasted_iota(jnp.int32, (n, k), 1)

    def step(j, carry):
        bufs, out_s, out_i = carry
        m = functools.reduce(jnp.maximum, [
            jnp.max(b, axis=1, keepdims=True) for b in bufs])      # [n, 1]
        sel = functools.reduce(jnp.minimum, [
            jnp.min(jnp.where(b == m, i, INT_MAX), axis=1, keepdims=True)
            for b, i in zip(bufs, parts_i)])                         # [n, 1]
        bufs = tuple(jnp.where((b == m) & (i == sel), NEG_INF, b)
                     for b, i in zip(bufs, parts_i))
        put = cols == j
        out_s = jnp.where(put, m, out_s)
        out_i = jnp.where(put, sel, out_i)
        return bufs, out_s, out_i

    init = (parts_s,
            jnp.full((n, k), NEG_INF, jnp.float32),
            jnp.full((n, k), -1, jnp.int32))
    _, out_s, out_i = jax.lax.fori_loop(0, k, step, init)
    return out_s, out_i


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


@functools.partial(jax.jit, static_argnames=("k_short", "row_block",
                                             "item_block"))
def topk_ref(
    w: jnp.ndarray,        # [n, d] user score vectors
    Minv: jnp.ndarray,     # [n, d, d]
    occ: jnp.ndarray,      # [n] i32
    items: jnp.ndarray,    # [N, d] catalog embeddings
    live: jnp.ndarray,     # [N] f32/bool liveness (0 = retired)
    alpha: float,
    k_short: int,
    *,
    row_block: int = 8,
    item_block: int = 4096,
    scales: jnp.ndarray | None = None,   # [N] f32 per-slot dequant scales
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(scores [n, k_short], ids [n, k_short] i32; dead/pad entries keep
    score -inf — the caller maps them to id -1).

    ``items`` may be stored bf16 or int8 (``Precision``): scoring always
    runs on the f32 dequantized stream ``items.astype(f32) * scales`` —
    for int8 the caller passes the catalog's per-slot scales; bf16/f32
    pass ``scales=None`` and the astype upcast is the whole dequant (a
    no-op for f32, keeping the default policy bit-identical).  ``Minv``
    may be bf16 and is upcast the same way."""
    n, d = w.shape
    N = items.shape[0]
    ib = min(item_block, _round_up(N, 8))
    Np = _round_up(N, ib)
    rb = min(row_block, n)
    npad = _round_up(n, rb)

    items_f = items.astype(jnp.float32)
    if scales is not None:
        items_f = items_f * scales.astype(jnp.float32)[:, None]
    items_p = jnp.pad(items_f, ((0, Np - N), (0, 0)))
    live_p = jnp.pad(live.astype(jnp.float32), (0, Np - N))
    mf = jnp.pad(Minv.astype(jnp.float32).reshape(n, d * d),
                 ((0, npad - n), (0, 0)))
    w_p = jnp.pad(w, ((0, npad - n), (0, 0)))
    widen = jnp.pad(jnp.sqrt(jnp.log1p(occ.astype(jnp.float32))),
                    (0, npad - n))
    tiles = Np // ib

    def block_fn(blk):
        w_b, mf_b, f_b = blk                     # [rb, d], [rb, d^2], [rb]

        def tile_step(carry, t):
            run_s, run_i = carry
            x = jax.lax.dynamic_slice_in_dim(items_p, t * ib, ib)
            lv = jax.lax.dynamic_slice_in_dim(live_p, t * ib, ib)
            G = (x[:, None, :] * x[:, :, None]).reshape(ib, d * d)
            est = w_b @ x.T                                     # [rb, ib]
            quad = mf_b @ G.T                                   # [rb, ib]
            s = est + alpha * jnp.sqrt(jnp.maximum(quad, 0.0)) * f_b[:, None]
            s = jnp.where(lv[None, :] > 0, s, NEG_INF)
            ids = t * ib + jnp.arange(ib, dtype=jnp.int32)
            buf_s = jnp.concatenate([run_s, s], axis=1)
            buf_i = jnp.concatenate(
                [run_i, jnp.broadcast_to(ids[None], (rb, ib))], axis=1)
            return select_topk(buf_s, buf_i, k_short), None

        init = (jnp.full((rb, k_short), NEG_INF, jnp.float32),
                jnp.full((rb, k_short), -1, jnp.int32))
        (out_s, out_i), _ = jax.lax.scan(
            tile_step, init, jnp.arange(tiles, dtype=jnp.int32))
        return out_s, out_i

    blocks = (w_p.reshape(npad // rb, rb, d),
              mf.reshape(npad // rb, rb, d * d),
              widen.reshape(npad // rb, rb))
    out_s, out_i = jax.lax.map(block_fn, blocks)
    return (out_s.reshape(npad, k_short)[:n],
            out_i.reshape(npad, k_short)[:n])


# ---------------------------------------------------------------------------
# cluster-pruned streaming: per-tile UCB upper bounds + tile skipping
# ---------------------------------------------------------------------------

# absolute safety margin added to every tile bound: the bound math and the
# per-item score use different f32 op orders, so without slack a rounding
# wiggle of ~1e-6 could nudge a true bound below a real score and break
# exactness.  1e-4 dwarfs any accumulation error at serving magnitudes
# (scores are O(1)) while costing essentially no pruning.
BOUND_SLACK = 1e-4

# relative margin on the eigenvalue majorant: its sums, products and square
# roots run in f32 and can land a few ulps times d (~1e-6 relative at
# serving widths) under the exact value; 1e-4 covers that many times over
# while widening sqrt(lmax) by only 5e-5.
LMAX_MARGIN = 1e-4
# squarings of the power-trace majorant, m = 2^16: it overshoots lmax by at
# most rank^(1/65536) (1 + 4.5e-5 at d=19), so the tile bounds are as tight
# as with the exact eigenvalue, for 15 batched d x d products a matrix.
LMAX_SQUARINGS = 16


def lmax_upper(A: jnp.ndarray) -> jnp.ndarray:
    """``[n]`` f32, at least the largest eigenvalue of the symmetric part
    of each PSD ``A[i]`` (``[n, d, d]``), from sums, products and square
    roots alone: the smaller of

      * Gershgorin, ``g = max_i sum_j |A_ij|``, exact for a diagonal
        ``A`` (a user with no history, ``Minv = I/lambda``);
      * the power trace, ``tr(A^m)^(1/m)`` with ``m = 2^P``, ``P =
        LMAX_SQUARINGS``: at most ``rank^(1/m)`` times ``lmax``, less for
        a spread spectrum.  With ``B_0 = A / c_0``, ``B_j = B_{j-1}^2 /
        c_j`` (``B_{P-1}`` is ``A^(m/2)`` scaled) and ``f =
        |B_{P-1}|_F^2``, ``tr(A^m)^(1/m) = c_0 sqrt(c_1 sqrt(c_2 ...
        sqrt(c_{P-1} sqrt(f))))``.  Each ``c_j`` is the power of two just
        above ``g`` (``j = 0``) or ``tr(B_{j-1}^2)``: dividing by it is
        exact and keeps every ``B_j`` within 1 in norm, so nothing
        overflows, and ``A = 0`` gives 0;

    times ``1 + LMAX_MARGIN``.  No eigensolver and no data-dependent
    iteration count: ``LMAX_SQUARINGS - 1`` batched products, fixed, each
    an f32 multiply-add over the shared index on the vector unit (no
    reduced-precision MXU pass).  No log or exp either: a TPU v5e's
    f32 log errs by up to 3.5e-4 relative, more than the margin."""
    A = 0.5 * (A + jnp.swapaxes(A, 1, 2))
    gersh = jnp.max(jnp.sum(jnp.abs(A), axis=2), axis=1)
    e = jnp.frexp(gersh)[1]              # gersh in [2^(e-1), 2^e); 0 -> 0
    B = jnp.ldexp(A, -e[:, None, None])
    exps = [e]
    for _ in range(1, LMAX_SQUARINGS):
        B = jnp.sum(B[:, :, :, None] * B[:, None, :, :], axis=2)
        e = jnp.frexp(jnp.trace(B, axis1=1, axis2=2))[1]
        B = jnp.ldexp(B, -e[:, None, None])
        exps.append(e)
    root = jnp.sqrt(jnp.sum(B * B, axis=(1, 2)))
    for e in reversed(exps[1:]):
        root = jnp.sqrt(jnp.ldexp(root, e))
    return (1.0 + LMAX_MARGIN) * jnp.minimum(gersh,
                                             jnp.ldexp(root, exps[0]))


@jax.jit
def tile_bounds(
    w: jnp.ndarray,        # [n, d] user score vectors
    Minv: jnp.ndarray,     # [n, d, d] SPD
    occ: jnp.ndarray,      # [n] i32
    alpha: float | jnp.ndarray,
    tile_mu: jnp.ndarray,  # [T, d] live-item tile centroids
    tile_r: jnp.ndarray,   # [T] max live |x - mu| per tile
    tile_xn: jnp.ndarray,  # [T] max live |x| per tile
    tile_n: jnp.ndarray,   # [T] i32 live items per tile
) -> jnp.ndarray:
    """[n, T] f32 — a TRUE upper bound on every live item score per tile:

        w.x                 <= w.mu + |w| r          (Cauchy-Schwarz)
        |x|_Minv            <= min(|mu|_Minv + sqrt(lmax) r, sqrt(lmax) xn)
                               (seminorm triangle ineq.; |v|_A <= sqrt(lmax)|v|
                                for any lmax >= the largest eigenvalue of
                                Minv: :func:`lmax_upper`'s majorant)

    so  tb = w.mu + |w| r + alpha sqrt(log1p(occ)) min(...) + BOUND_SLACK
    dominates ``score[u, i]`` for every live ``i`` in the tile.  ``mu``
    is just a reference point — the bound holds for the STORED centroid
    whatever rounding produced it, as long as ``r >= max |x - mu|``.
    Zero-live tiles bound to -inf (skippable as soon as any floor
    exists).  The min keeps the bound tight both when a cluster is
    compact (centroid term) and when Minv is diffuse (max-norm term)."""
    n, d = w.shape
    T = tile_mu.shape[0]
    Minv = Minv.astype(jnp.float32)
    sl = jnp.sqrt(lmax_upper(Minv))                     # [n]
    # HIGHEST: a bf16 matmul pass (the TPU default for f32) errs by far
    # more than BOUND_SLACK, and the bound must dominate the f32 scores
    hi = jax.lax.Precision.HIGHEST
    est = (jnp.dot(w, tile_mu.T, precision=hi)
           + jnp.linalg.norm(w, axis=1)[:, None] * tile_r[None])
    G = (tile_mu[:, None, :] * tile_mu[:, :, None]).reshape(T, d * d)
    qmu = jnp.sqrt(jnp.maximum(
        jnp.dot(Minv.reshape(n, d * d), G.T, precision=hi), 0.0))
    conf = jnp.minimum(qmu + sl[:, None] * tile_r[None],
                       sl[:, None] * tile_xn[None])
    widen = jnp.sqrt(jnp.log1p(occ.astype(jnp.float32)))
    tb = est + alpha * conf * widen[:, None] + BOUND_SLACK
    return jnp.where(tile_n[None] > 0, tb, NEG_INF)


@functools.partial(jax.jit, static_argnames=("k_short", "row_block"))
def topk_ref_pruned(
    w: jnp.ndarray,        # [n, d]
    Minv: jnp.ndarray,     # [n, d, d]
    occ: jnp.ndarray,      # [n] i32
    items: jnp.ndarray,    # [N, d] cluster-SORTED catalog embeddings
    live: jnp.ndarray,     # [N] f32/bool liveness in sorted order
    ids: jnp.ndarray,      # [N] i32 GLOBAL slot id of each sorted row
    alpha: float,
    k_short: int,
    tb: jnp.ndarray,       # [n, T] tile upper bounds (tile = N // T)
    *,
    row_block: int = 8,
    scales: jnp.ndarray | None = None,   # [N] f32 per-slot dequant scales
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """(scores [n, k_short], ids [n, k_short] — BIT-EQUAL to the
    unpruned shortlist over the unsorted catalog — plus
    (tiles_skipped [], tile_visits_total []) i32 skip telemetry).

    Selection buffers carry the ORIGINAL slot ids, so tie-breaks are by
    slot id exactly as in the unpruned stream — the (score, id) multiset
    is identical and :func:`select_topk` is value-based, hence
    bit-equality.  Two orderings make skipping actually fire: users are
    grouped into row blocks by their best-bound tile (per-user results
    are independent, so permuting and un-permuting rows is exact), and
    each block visits tiles in descending block-max bound order so the
    shortlist floor is high before doubtful tiles are tested.  The skip
    branch is a real ``lax.cond`` — a skipped tile's scoring work is
    never executed, which is the wall-clock (and modeled-HBM) win."""
    n, d = w.shape
    N = items.shape[0]
    T = tb.shape[1]
    assert N % T == 0, (N, T)
    ib = N // T
    rb = min(row_block, n)
    npad = _round_up(n, rb)

    # group users whose best tile coincides: a row block only skips a
    # tile when ALL of its users agree, so coherence is the lever
    order = jnp.argsort(jnp.argmax(tb, axis=1), stable=True).astype(jnp.int32)
    inv = jnp.argsort(order).astype(jnp.int32)
    pad_u = npad - n
    w_p = jnp.pad(w[order], ((0, pad_u), (0, 0)))
    mf = jnp.pad(Minv.astype(jnp.float32).reshape(n, d * d)[order],
                 ((0, pad_u), (0, 0)))
    widen = jnp.pad(jnp.sqrt(jnp.log1p(occ.astype(jnp.float32)))[order],
                    (0, pad_u))
    # padded users bound every tile at -inf: they vote "skip" as soon as
    # their (all-zero-statistics) floor leaves -inf, so they never keep a
    # tile alive that the real users would prune
    tb_p = jnp.pad(tb[order], ((0, pad_u), (0, 0)),
                   constant_values=NEG_INF)
    items_f = items.astype(jnp.float32)
    if scales is not None:
        items_f = items_f * scales.astype(jnp.float32)[:, None]
    live_f = live.astype(jnp.float32)
    ids_i = ids.astype(jnp.int32)

    def block_fn(blk):
        w_b, mf_b, f_b, tb_b = blk        # [rb,d] [rb,d^2] [rb] [rb,T]
        # likeliest tiles first: the floor saturates within the first
        # visited tiles, then everything that cannot beat it skips
        tile_order = jnp.argsort(-jnp.max(tb_b, axis=0)).astype(jnp.int32)

        def tile_step(carry, j):
            run_s, run_i, skipped = carry
            t = tile_order[j]
            floor = run_s[:, k_short - 1]
            skip = jnp.all(tb_b[:, t] < floor)     # STRICT: ties rescore

            def do_skip(c):
                rs, ri, sk = c
                return rs, ri, sk + 1

            def do_score(c):
                rs, ri, sk = c
                x = jax.lax.dynamic_slice_in_dim(items_f, t * ib, ib)
                lv = jax.lax.dynamic_slice_in_dim(live_f, t * ib, ib)
                iv = jax.lax.dynamic_slice_in_dim(ids_i, t * ib, ib)
                G = (x[:, None, :] * x[:, :, None]).reshape(ib, d * d)
                est = w_b @ x.T
                quad = mf_b @ G.T
                s = est + alpha * jnp.sqrt(
                    jnp.maximum(quad, 0.0)) * f_b[:, None]
                s = jnp.where(lv[None, :] > 0, s, NEG_INF)
                buf_s = jnp.concatenate([rs, s], axis=1)
                buf_i = jnp.concatenate(
                    [ri, jnp.broadcast_to(iv[None], (rb, ib))], axis=1)
                out_s, out_i = select_topk(buf_s, buf_i, k_short)
                return out_s, out_i, sk

            return jax.lax.cond(skip, do_skip, do_score,
                                (run_s, run_i, skipped)), None

        init = (jnp.full((rb, k_short), NEG_INF, jnp.float32),
                jnp.full((rb, k_short), -1, jnp.int32),
                jnp.zeros((), jnp.int32))
        (out_s, out_i, sk), _ = jax.lax.scan(
            tile_step, init, jnp.arange(T, dtype=jnp.int32))
        return out_s, out_i, sk

    blocks = (w_p.reshape(npad // rb, rb, d),
              mf.reshape(npad // rb, rb, d * d),
              widen.reshape(npad // rb, rb),
              tb_p.reshape(npad // rb, rb, T))
    out_s, out_i, sk = jax.lax.map(block_fn, blocks)
    total = jnp.asarray(T * (npad // rb), jnp.int32)
    return (out_s.reshape(npad, k_short)[:n][inv],
            out_i.reshape(npad, k_short)[:n][inv],
            jnp.sum(sk).astype(jnp.int32), total)
