"""Public entry point for the fused rank-1 bandit-state update."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..pad import SUB, round_up, user_block
from .rank1 import rank1_update_inv_pallas
from .ref import rank1_update_inv_ref


def _dims(n: int, d: int, block_users: int):
    """(n_pad, d_pad, kernel block): users pad to the padding block, and
    the kernel runs a divisor of it that keeps the [Bu, d, d] working set
    (double-buffered in and out) well inside VMEM."""
    np_, bu = user_block(n, block_users)
    return np_, round_up(d, SUB), math.gcd(bu, 64)


def rank1_update_inv(
    Minv, b, x, r, mask,
    *,
    use_pallas: bool | None = None,
    block_users: int = 256,
    interpret: bool | None = None,
):
    """(Minv', b') — M-free fused update for the sharded runtime."""
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    if not use_pallas:
        return rank1_update_inv_ref(Minv, b, x, r, mask)

    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n, d = b.shape
    np_, dp, bu = _dims(n, d, block_users)

    if (n, d) == (np_, dp):
        return rank1_update_inv_pallas(
            Minv, b, x, r, mask.astype(jnp.float32),
            block_users=bu, interpret=interpret,
        )

    Minvp = jnp.zeros((np_, dp, dp), Minv.dtype).at[:n, :d, :d].set(Minv)
    i = jnp.arange(d, dp)
    Minvp = Minvp.at[:, i, i].set(jnp.ones((), Minv.dtype))
    bp = jnp.zeros((np_, dp), jnp.float32).at[:n, :d].set(b)
    xp = jnp.zeros((np_, dp), jnp.float32).at[:n, :d].set(x)
    rp = jnp.zeros((np_,), jnp.float32).at[:n].set(r)
    mp = jnp.zeros((np_,), jnp.float32).at[:n].set(mask.astype(jnp.float32))

    Minvo, bo = rank1_update_inv_pallas(
        Minvp, bp, xp, rp, mp, block_users=bu, interpret=interpret
    )
    return Minvo[:n, :d, :d], bo[:n, :d]
