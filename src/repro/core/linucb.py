"""Vectorized linear-contextual-bandit primitives (the per-user math).

The paper's UCB rule (Listing 1) for a context set K = [k_1..k_K]:

    estimate_j = k_j . w
    bonus_j    = alpha * sqrt(k_j^T Minv k_j) * sqrt(log(1 + occ))
    choice     = argmax_j estimate_j + bonus_j

and the standard rank-1 statistics update

    M += x x^T ;  b += r * x.

We maintain Minv incrementally by Sherman-Morrison (exact for rank-1
updates) instead of re-inverting M — a beyond-paper optimization that turns
the per-interaction cost from O(d^3) to O(d^2).  ``tests/test_linucb.py``
checks it against explicit solves.

The batched versions below are the *reference* implementations; the Pallas
kernels in ``repro.kernels.interact`` / ``repro.kernels.rank1`` implement the
same contracts for the TPU hot path and are validated against these.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .types import LinUCBState


def init_linucb(n_users: int, d: int, dtype=jnp.float32) -> LinUCBState:
    eye = jnp.broadcast_to(jnp.eye(d, dtype=dtype), (n_users, d, d))
    return LinUCBState(
        M=eye,
        Minv=eye,
        b=jnp.zeros((n_users, d), dtype),
        occ=jnp.zeros((n_users,), jnp.int32),
    )


def user_vector(Minv: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """v = Minv @ b.  Works for single ([d,d],[d]) or batched ([...,d,d],[...,d])."""
    return jnp.einsum("...ij,...j->...i", Minv, b)


def ucb_scores(
    w: jnp.ndarray,          # [d] preference estimate used for exploitation
    Minv: jnp.ndarray,       # [d, d] inverse Gram used for the bonus
    contexts: jnp.ndarray,   # [K, d] candidate item features
    occ: jnp.ndarray,        # [] i32 interaction count
    alpha: float,
) -> jnp.ndarray:
    """Paper's UCB(w, occ, context, Minv): returns [K] scores.

    Written as elementwise products and reductions, not matmuls: every
    candidate row goes through the same arithmetic wherever it sits, so
    identical candidates score bit-identically (a blocked matmul may round
    rows in different micro-kernels differently) and the first-index
    argmax tie-break is exact — the fused choose kernel relies on the
    same property.  It also keeps the score f32-exact on TPU whatever the
    default matmul precision."""
    estimate = jnp.sum(contexts * w, axis=-1)
    t = jnp.sum(Minv[None, :, :] * contexts[:, None, :], axis=-1)   # [K, d]
    quad = jnp.sum(contexts * t, axis=-1)
    bonus = alpha * jnp.sqrt(jnp.maximum(quad, 0.0)) * jnp.sqrt(
        jnp.log1p(occ.astype(contexts.dtype))
    )
    return estimate + bonus


def choose(w, Minv, contexts, occ, alpha) -> jnp.ndarray:
    """argmax over the candidate axis; returns [] i32 index."""
    return jnp.argmax(ucb_scores(w, Minv, contexts, occ, alpha))


# Batched (over users) versions ------------------------------------------------

ucb_scores_batch = jax.vmap(ucb_scores, in_axes=(0, 0, 0, 0, None))
choose_batch = jax.vmap(choose, in_axes=(0, 0, 0, 0, None))


def sherman_morrison(Minv: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """(M + x x^T)^-1 from M^-1, for [..., d, d] and [..., d]."""
    Mx = jnp.einsum("...ij,...j->...i", Minv, x)              # [..., d]
    denom = 1.0 + jnp.einsum("...i,...i->...", x, Mx)          # [...]
    outer = jnp.einsum("...i,...j->...ij", Mx, Mx)             # [..., d, d]
    return Minv - outer / denom[..., None, None]


def rank1_update(
    state: LinUCBState,
    user: jnp.ndarray,       # [] i32
    x: jnp.ndarray,          # [d]
    reward: jnp.ndarray,     # []
) -> LinUCBState:
    """Single-interaction update of one user's statistics (functional)."""
    M = state.M.at[user].add(jnp.outer(x, x))
    Minv = state.Minv.at[user].set(sherman_morrison(state.Minv[user], x))
    b = state.b.at[user].add(reward * x)
    occ = state.occ.at[user].add(1)
    return LinUCBState(M, Minv, b, occ)


def masked_batch_update(
    state: LinUCBState,
    x: jnp.ndarray,        # [n, d] one chosen context per user this step
    reward: jnp.ndarray,   # [n]
    mask: jnp.ndarray,     # [n] bool -- users actually active this step
) -> LinUCBState:
    """One interaction for every active user, in parallel.

    Distinct users never alias, so a full-width masked update is exact: it is
    the batched equivalent of the paper's per-user serialized processing
    (serialization across *steps*, parallelism across *users*).
    """
    m = mask.astype(x.dtype)
    xm = x * m[:, None]                       # zero context => identity update
    M = state.M + jnp.einsum("ni,nj->nij", xm, xm)
    Minv = sherman_morrison(state.Minv, xm)
    b = state.b + (reward * m)[:, None] * x
    occ = state.occ + mask.astype(jnp.int32)
    return LinUCBState(M, Minv, b, occ)
