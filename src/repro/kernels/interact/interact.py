"""Fused choose Pallas kernel — the bandit interaction hot path.

One grid step scores a *block of users* end to end:

    score[u,k]  = ctx[u,k,:].w[u] + alpha sqrt(ctx Minv ctx) sqrt(log1p(occ[u]))
    choice[u]   = argmax_k score[u,k]          (first index on ties)

The ``[n, K]`` score tensor lives and dies in VMEM: the kernel reads each
user's (w, Minv, ctx, occ) once and writes only ``choice``.  The chosen
context ``x[u] = ctx[u, choice[u]]`` is an exact row gather of ``n d``
words done by XLA next to the call.

TPU layout: the wrapper hands the kernel the contexts transposed to
``[n, d, K]`` so the (lane-padded) candidate axis sits on the 128 lanes
and every per-user contraction is a VPU multiply-add over sublanes:

    est[u,k]  = sum_j ctx[u,j,k] w[u,j]
    t[u,i,k]  = sum_j Minv[u,i,j] ctx[u,j,k]     (d unrolled FMAs)
    quad[u,k] = sum_i ctx[u,i,k] t[u,i,k]

No batched ``dot_general`` is needed (Mosaic rejects its batch-dim
attribute), and each candidate's score is computed by the same lane-wise
arithmetic wherever it sits, so duplicate candidates score bit-identically
and the first-index tie-break is exact.

Padded candidates (K rounded up to the lane multiple by ``ops.py``) are
masked to -inf *inside* the kernel so a zero-padded candidate (score 0) can
never beat a real candidate with a negative score; padded feature rows are
exact by the zero-column argument.  ``alpha`` rides in SMEM; ``occ`` and
``choice`` are ``[n, 1]`` columns (1-D blocks do not match XLA's layout).

VMEM per grid step (f32 words): ctx + t (2 Bu d K) + Minv (Bu d 128, lane
padded) + scores (Bu K).  At Bu=32, K=128, d=32: ~1.5 MiB, double-buffered
inputs included.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _choose_kernel(w_ref, minv_ref, ctx_ref, occ_ref, alpha_ref, choice_ref,
                   *, k_live: int):
    c = ctx_ref[...]                            # [Bu, d, K] candidates on lanes
    # Minv may be stored bf16 (Precision state_dtype); upcast in VMEM so
    # the arithmetic runs f32 (no-op for f32 inputs).
    minv = minv_ref[...].astype(jnp.float32)    # [Bu, d, d]
    w = w_ref[...]                              # [Bu, d]
    d = c.shape[1]

    est = jnp.sum(c * w[:, :, None], axis=1)                 # [Bu, K]
    t = minv[:, :, 0:1] * c[:, 0:1, :]
    for j in range(1, d):
        t = t + minv[:, :, j:j + 1] * c[:, j:j + 1, :]       # [Bu, d, K]
    quad = jnp.sum(c * t, axis=1)                            # [Bu, K]
    widen = jnp.sqrt(jnp.log1p(occ_ref[...].astype(jnp.float32)))  # [Bu, 1]
    scores = est + alpha_ref[0] * jnp.sqrt(jnp.maximum(quad, 0.0)) * widen

    kidx = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    scores = jnp.where(kidx < k_live, scores, -jnp.inf)
    best = jnp.max(scores, axis=1, keepdims=True)
    choice_ref[...] = jnp.min(jnp.where(scores == best, kidx, scores.shape[1]),
                              axis=1, keepdims=True)


@functools.partial(jax.jit,
                   static_argnames=("k_live", "block_users", "interpret"))
def choose_pallas(
    w: jnp.ndarray,          # [n, d]   (n % block_users == 0; pad in ops.py)
    Minv: jnp.ndarray,       # [n, d, d]
    contexts: jnp.ndarray,   # [n, K, d]
    occ: jnp.ndarray,        # [n] i32
    alpha: float,
    k_live: int,             # candidates beyond this index are padding
    *,
    block_users: int = 32,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (choice [n] i32, x [n, d]) — scores never touch HBM."""
    n, K, d = contexts.shape
    assert n % block_users == 0, (n, block_users)
    bu = block_users
    choice = pl.pallas_call(
        functools.partial(_choose_kernel, k_live=k_live),
        grid=(n // bu,),
        in_specs=[
            pl.BlockSpec((bu, d), lambda i: (i, 0)),
            pl.BlockSpec((bu, d, d), lambda i: (i, 0, 0)),
            pl.BlockSpec((bu, d, K), lambda i: (i, 0, 0)),
            pl.BlockSpec((bu, 1), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((bu, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, 1), jnp.int32),
        interpret=interpret,
        name="choose",
    )(w, Minv, jnp.swapaxes(contexts, 1, 2), occ.reshape(n, 1),
      jnp.asarray(alpha, jnp.float32).reshape(1))[:, 0]
    x = jnp.take_along_axis(contexts, choice[:, None, None], axis=1)[:, 0]
    return choice, x
