"""Pure-jnp oracle for the fused choose kernel.

Kept in terms of ``linucb.choose_batch`` semantics: score
(:func:`ucb_scores_ref`), first-index argmax, gather.  This is also the
CPU/GPU execution path when the Pallas backend is off.
"""
from __future__ import annotations

import jax.numpy as jnp


def ucb_scores_ref(
    w: jnp.ndarray,         # [n, d]
    Minv: jnp.ndarray,      # [n, d, d]
    contexts: jnp.ndarray,  # [n, K, d]
    occ: jnp.ndarray,       # [n] i32
    alpha: float,
) -> jnp.ndarray:
    """scores[n, K] = contexts.w + alpha sqrt(ctx Minv ctx) sqrt(log1p(occ))."""
    # elementwise form shared with ``core.linucb.ucb_scores``: identical
    # candidates score bit-identically (see there)
    est = jnp.sum(contexts * w[:, None, :], axis=-1)
    t = jnp.sum(Minv[:, None, :, :] * contexts[:, :, None, :], axis=-1)
    quad = jnp.sum(contexts * t, axis=-1)
    bonus = alpha * jnp.sqrt(jnp.maximum(quad, 0.0)) * jnp.sqrt(
        jnp.log1p(occ.astype(contexts.dtype))
    )[:, None]
    return est + bonus


def choose_ref(
    w: jnp.ndarray,          # [n, d]
    Minv: jnp.ndarray,       # [n, d, d]
    contexts: jnp.ndarray,   # [n, K, d]
    occ: jnp.ndarray,        # [n] i32
    alpha: float,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (choice [n] i32, x [n, d])."""
    # Minv may be stored bf16 (Precision); score in f32 like the kernel.
    scores = ucb_scores_ref(w, Minv.astype(jnp.float32), contexts, occ,
                            alpha)
    choice = jnp.argmax(scores, axis=-1).astype(jnp.int32)
    x = jnp.take_along_axis(contexts, choice[:, None, None], axis=1)[:, 0]
    return choice, x
