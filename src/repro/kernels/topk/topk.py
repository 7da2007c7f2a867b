"""Streaming UCB top-K Pallas kernel — catalog-scale retrieval.

One grid column serves a *block of users* against the whole item catalog:
the grid is ``(n / Bu, N / Bt)`` with the item axis innermost, so each
step streams one ``[Bt, d]`` catalog tile into VMEM, scores it for the
user block, and folds it into a running ``[Bu, k_short]`` shortlist held
in the (revisited) output blocks — exactly the ``cc_hop`` revisit pattern
of the graph engine.  The payoff is the whole point of the retrieval
engine: the ``[n, N_items]`` score matrix is never formed anywhere — not
in HBM, not even in VMEM — so serving against ``N_items ~ 2**20`` costs
the catalog stream (amortized over the user block) plus ``O(k_short)``
words of output per user instead of ``O(N_items)``.

Per tile the kernel computes

    est  = w @ x'                     [Bu, Bt]   (MXU)
    quad = vec(Minv) @ vec(x x')'     [Bu, Bt]   (MXU, d^2 contraction)
    s    = est + alpha sqrt(max(quad, 0)) sqrt(log1p(occ))   (VPU)

— the identical UCB the fused choose kernel scores a slate with, so the
two-stage recommend path re-ranks the shortlist with the same statistics
it was selected by.  Dead items (``live == 0``) and tile padding score
-inf.  The running shortlist is merged with the tile by
``ref.select_topk`` — repeated (max score, min id) selection, value-based
and therefore invariant to tile order/size — which the jnp oracle uses
verbatim; see ``ref.py`` for why that makes reference/pallas/sharded
shortlists identical.

TPU layout.  Per-item vectors (``live``, ``ids``, int8 ``scales``) ride as
``[1, N]`` rows and per-user ones (``occ``) as ``[n, 1]`` columns — 1-D
blocks do not match XLA's layout.  ``alpha`` sits in SMEM.  ``Minv``
arrives flattened to ``[n, d^2]`` (an XLA reshape next to the call), and
the tile Gram ``vec(x x')`` is built on the MXU from two 0/1 selection
matrices, ``(x E1) * (x E2)``, so no in-kernel reshape is needed; each
product is one f32 multiply, bit-equal to the reference's.  The running
shortlist and the new tile go to ``select_topk`` as two parts instead of
one lane-concatenated buffer.  All contractions ask for HIGHEST
precision: f32 scores, not bf16 passes.

VMEM per step (f32 words, defaults Bu=128, Bt=512, d<=32): Gram tile
``Bt d^2`` (2 MiB at d=32) + ``Minv`` ``Bu d^2`` (0.5 MiB) + selection
matrices ``2 d^3`` (0.25 MiB) + score/merge buffers ``~4 Bu (k_short +
Bt)`` (~1.2 MiB at k_short=64) — well under the 16 MiB scoped budget.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import NEG_INF, select_topk

_HI = jax.lax.Precision.HIGHEST

# The pruned variant (`topk_pruned_pallas`) streams the cluster-SORTED
# catalog with a [Bu, T] tile-bound table resident per user block: before
# a tile's compute fires, the bound column is compared against the
# running shortlist floor and `pl.when` predicates the whole
# score+merge step off when no user in the block can be improved —
# the tile's MXU work is skipped and a revisited counter block
# accumulates how many tiles were.  Tiles arrive in natural order (the
# reference path's bound-descending visit order needs data-dependent
# index maps — `pltpu.PrefetchScalarGridSpec`, future TPU work), so the
# skip ratio trails the reference oracle's; exactness does not: per-item
# score bits and the value-based `select_topk` fold are identical, and
# the selection buffers carry ORIGINAL slot ids, so ties break exactly
# as in the unpruned stream.


def _nt(a, b):
    """a [m, k] . b [n, k]' -> [m, n] in f32."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())), precision=_HI,
                               preferred_element_type=jnp.float32)


def _tile_scores(w_ref, mf_ref, occ_ref, items_ref, live_ref, scale_ref,
                 e1_ref, e2_ref, alpha_ref):
    """[Bu, Bt] UCB scores of one catalog tile (dead items -inf)."""
    x = items_ref[...].astype(jnp.float32)     # [Bt, d] (bf16/int8 ok)
    if scale_ref is not None:
        # int8 dequant in VMEM: the [1, Bt] scale row becomes a column
        # through an identity mask (exact: it adds zeros)
        bt = x.shape[0]
        eye = (jax.lax.broadcasted_iota(jnp.int32, (bt, bt), 0)
               == jax.lax.broadcasted_iota(jnp.int32, (bt, bt), 1))
        x = x * jnp.sum(jnp.where(eye, scale_ref[...], 0.0), axis=1,
                        keepdims=True)
    est = _nt(w_ref[...], x)                                 # [Bu, Bt]
    G = (jnp.dot(x, e1_ref[...], precision=_HI,
                 preferred_element_type=jnp.float32)
         * jnp.dot(x, e2_ref[...], precision=_HI,
                   preferred_element_type=jnp.float32))      # [Bt, d^2]
    quad = _nt(mf_ref[...].astype(jnp.float32), G)           # [Bu, Bt]
    widen = jnp.sqrt(jnp.log1p(occ_ref[...].astype(jnp.float32)))  # [Bu, 1]
    s = est + alpha_ref[0] * jnp.sqrt(jnp.maximum(quad, 0.0)) * widen
    return jnp.where(live_ref[...] > 0, s, NEG_INF)


def _fold(sc_ref, id_ref, s, ids, k_short):
    out_s, out_i = select_topk((sc_ref[...], s), (id_ref[...], ids), k_short)
    sc_ref[...] = out_s
    id_ref[...] = out_i


def _init(sc_ref, id_ref, k_short):
    bu = sc_ref.shape[0]
    sc_ref[...] = jnp.full((bu, k_short), NEG_INF, jnp.float32)
    id_ref[...] = jnp.full((bu, k_short), -1, jnp.int32)


def _topk_kernel(*refs, k_short: int, has_scales: bool):
    # With has_scales (int8 catalog) a per-slot scale row rides along
    # after `live`; f32/bf16 programs take no extra input.
    if has_scales:
        (w_ref, mf_ref, occ_ref, items_ref, live_ref, scale_ref,
         e1_ref, e2_ref, alpha_ref, sc_ref, id_ref) = refs
    else:
        (w_ref, mf_ref, occ_ref, items_ref, live_ref,
         e1_ref, e2_ref, alpha_ref, sc_ref, id_ref) = refs
        scale_ref = None
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _():
        _init(sc_ref, id_ref, k_short)

    s = _tile_scores(w_ref, mf_ref, occ_ref, items_ref, live_ref, scale_ref,
                     e1_ref, e2_ref, alpha_ref)
    ids = t * s.shape[1] + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    _fold(sc_ref, id_ref, s, ids, k_short)


def _gram_selectors(d: int):
    """0/1 [d, d^2] matrices with (x E1)[a d + b] = x_a, (x E2)[a d + b] = x_b."""
    eye = jnp.eye(d, dtype=jnp.float32)
    return jnp.repeat(eye, d, axis=1), jnp.tile(eye, (1, d))


def _user_operands(w, Minv, occ):
    n, d = w.shape
    return [w, Minv.reshape(n, d * d), occ.reshape(n, 1)]


def _user_specs(block_users, d):
    return [
        pl.BlockSpec((block_users, d), lambda i, t: (i, 0)),
        pl.BlockSpec((block_users, d * d), lambda i, t: (i, 0)),
        pl.BlockSpec((block_users, 1), lambda i, t: (i, 0)),
    ]


def _tail(d, alpha):
    """Operands + specs shared by both kernels after the per-item rows:
    the Gram selectors (resident: constant block index) and alpha (SMEM)."""
    e1, e2 = _gram_selectors(d)
    sel = pl.BlockSpec((d, d * d), lambda i, t: (0, 0))
    return ([e1, e2, jnp.asarray(alpha, jnp.float32).reshape(1)],
            [sel, sel, pl.BlockSpec(memory_space=pltpu.SMEM)])


@functools.partial(jax.jit,
                   static_argnames=("k_short", "block_users", "block_items",
                                    "interpret"))
def topk_pallas(
    w: jnp.ndarray,        # [n, d]    (n % block_users == 0; pad in ops.py)
    Minv: jnp.ndarray,     # [n, d, d]
    occ: jnp.ndarray,      # [n] i32
    items: jnp.ndarray,    # [N, d]    (N % block_items == 0)
    live: jnp.ndarray,     # [N] f32   (0 = retired/padding -> -inf)
    alpha: float,
    k_short: int,
    *,
    block_users: int = 128,
    block_items: int = 512,
    interpret: bool = False,
    scales: jnp.ndarray | None = None,   # [N] f32 int8 dequant scales
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(scores [n, k_short], ids [n, k_short] i32) — the [n, N] score
    matrix never exists; the running shortlist lives in revisited output
    blocks across the item-tile grid axis."""
    n, d = w.shape
    N = items.shape[0]
    assert n % block_users == 0, (n, block_users)
    assert N % block_items == 0, (N, block_items)
    grid = (n // block_users, N // block_items)
    row = pl.BlockSpec((1, block_items), lambda i, t: (0, t))

    in_specs = _user_specs(block_users, d) + [
        pl.BlockSpec((block_items, d), lambda i, t: (t, 0)), row]
    operands = _user_operands(w, Minv, occ) + [items, live.reshape(1, N)]
    if scales is not None:
        in_specs.append(row)
        operands.append(scales.reshape(1, N))
    tail_ops, tail_specs = _tail(d, alpha)

    return pl.pallas_call(
        functools.partial(_topk_kernel, k_short=k_short,
                          has_scales=scales is not None),
        grid=grid,
        in_specs=in_specs + tail_specs,
        out_specs=[
            pl.BlockSpec((block_users, k_short), lambda i, t: (i, 0)),
            pl.BlockSpec((block_users, k_short), lambda i, t: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, k_short), jnp.float32),
            jax.ShapeDtypeStruct((n, k_short), jnp.int32),
        ],
        interpret=interpret,
        name="topk",
    )(*operands, *tail_ops)


def _topk_pruned_kernel(*refs, k_short: int, has_scales: bool):
    if has_scales:
        (w_ref, mf_ref, occ_ref, items_ref, live_ref, ids_ref, scale_ref,
         tb_ref, e1_ref, e2_ref, alpha_ref, sc_ref, id_ref, sk_ref) = refs
    else:
        (w_ref, mf_ref, occ_ref, items_ref, live_ref, ids_ref,
         tb_ref, e1_ref, e2_ref, alpha_ref, sc_ref, id_ref, sk_ref) = refs
        scale_ref = None
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _():
        _init(sc_ref, id_ref, k_short)
        sk_ref[...] = jnp.zeros(sk_ref.shape, jnp.int32)

    # this tile's bound column, picked by a lane mask (no dynamic lane slice)
    tb = tb_ref[...]                                          # [Bu, T]
    lane = jax.lax.broadcasted_iota(jnp.int32, tb.shape, 1)
    bound = jnp.max(jnp.where(lane == t, tb, NEG_INF), axis=1, keepdims=True)
    floor = sc_ref[:, k_short - 1:k_short]                    # [Bu, 1]
    # STRICT <: a bound equal to the floor may hold an equal-score item
    # with a smaller id, which would displace the floor entry
    skip = jnp.all(bound < floor)
    sk_ref[...] = sk_ref[...] + skip.astype(jnp.int32)

    @pl.when(jnp.logical_not(skip))
    def _():
        s = _tile_scores(w_ref, mf_ref, occ_ref, items_ref, live_ref,
                         scale_ref, e1_ref, e2_ref, alpha_ref)
        ids = jnp.broadcast_to(ids_ref[...], s.shape)
        _fold(sc_ref, id_ref, s, ids, k_short)


@functools.partial(jax.jit,
                   static_argnames=("k_short", "block_users", "block_items",
                                    "interpret"))
def topk_pruned_pallas(
    w: jnp.ndarray,        # [n, d]        (n % block_users == 0)
    Minv: jnp.ndarray,     # [n, d, d]
    occ: jnp.ndarray,      # [n] i32
    items: jnp.ndarray,    # [N, d] cluster-sorted (N % block_items == 0)
    live: jnp.ndarray,     # [N] f32
    ids: jnp.ndarray,      # [N] i32 global slot ids of the sorted rows
    tb: jnp.ndarray,       # [n, T] tile bounds, T == N // block_items
    alpha: float,
    k_short: int,
    *,
    block_users: int = 128,
    block_items: int = 512,
    interpret: bool = False,
    scales: jnp.ndarray | None = None,   # [N] f32, sorted order
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """(scores [n, k_short], ids [n, k_short] i32,
    skipped [n // block_users] i32 — tiles skipped per user block)."""
    n, d = w.shape
    N = items.shape[0]
    assert n % block_users == 0, (n, block_users)
    assert N % block_items == 0, (N, block_items)
    T = N // block_items
    assert tb.shape == (n, T), (tb.shape, n, T)
    nb = n // block_users
    grid = (nb, T)
    row = pl.BlockSpec((1, block_items), lambda i, t: (0, t))

    in_specs = _user_specs(block_users, d) + [
        pl.BlockSpec((block_items, d), lambda i, t: (t, 0)), row, row]
    operands = _user_operands(w, Minv, occ) + [
        items, live.reshape(1, N), ids.reshape(1, N)]
    if scales is not None:
        in_specs.append(row)
        operands.append(scales.reshape(1, N))
    in_specs.append(pl.BlockSpec((block_users, T), lambda i, t: (i, 0)))
    operands.append(tb)
    tail_ops, tail_specs = _tail(d, alpha)

    scores, out_ids, sk = pl.pallas_call(
        functools.partial(_topk_pruned_kernel, k_short=k_short,
                          has_scales=scales is not None),
        grid=grid,
        in_specs=in_specs + tail_specs,
        out_specs=[
            pl.BlockSpec((block_users, k_short), lambda i, t: (i, 0)),
            pl.BlockSpec((block_users, k_short), lambda i, t: (i, 0)),
            # one (8, 128) tile per user block: the smallest legal block;
            # every entry carries the block's count
            pl.BlockSpec((8, 128), lambda i, t: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, k_short), jnp.float32),
            jax.ShapeDtypeStruct((n, k_short), jnp.int32),
            jax.ShapeDtypeStruct((nb * 8, 128), jnp.int32),
        ],
        interpret=interpret,
        name="topk_pruned",
    )(*operands, *tail_ops)
    return scores, out_ids, sk[::8, 0]
