"""Public entry point for the fused choose: pads, dispatches, unpads.

Padding policy (``kernels/pad.padded_dims``): d to the f32 sublane
multiple, K to the lane multiple, users to the block multiple.  When the
caller already holds padded arrays (the backend engine pads state once per
stage), every pad here is a trace-time no-op — no copies are issued.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..pad import padded_dims
from .interact import choose_pallas
from .ref import choose_ref


def choose(
    w: jnp.ndarray,          # [n, d]
    Minv: jnp.ndarray,       # [n, d, d]
    contexts: jnp.ndarray,   # [n, K, d]
    occ: jnp.ndarray,        # [n] i32
    alpha: float,
    *,
    use_pallas: bool | None = None,
    block_users: int = 256,
    interpret: bool | None = None,
    k_live: int | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(choice [n] i32, x [n, d]).  Pallas on TPU, jnp oracle elsewhere.

    Padded candidates are masked to -inf inside the kernel; padded feature
    columns are exact (zero contribution); padded users are sliced off.
    ``k_live`` tells the kernel how many candidates are real when the caller
    hands in pre-padded contexts (defaults to the context K axis).
    """
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    if not use_pallas:
        return choose_ref(w, Minv, contexts, occ, alpha)

    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n, K, d = contexts.shape
    if k_live is None:
        k_live = K
    np_, dp, Kp, bu = padded_dims(n, d, K, block_users)

    if (n, K, d) == (np_, Kp, dp):
        wp, Mp, cp, op = w, Minv, contexts, occ     # already aligned
    else:
        wp = jnp.zeros((np_, dp), jnp.float32).at[:n, :d].set(w)
        Mp = jnp.zeros((np_, dp, dp), Minv.dtype).at[:n, :d, :d].set(Minv)
        cp = jnp.zeros((np_, Kp, dp), jnp.float32).at[:n, :K, :d].set(contexts)
        op = jnp.zeros((np_,), occ.dtype).at[:n].set(occ)

    # the padding block bounds HBM layout; the kernel's VMEM block is a
    # divisor of it small enough for the [Bu, d, K] working set
    choice, x = choose_pallas(
        wp, Mp, cp, op, alpha, k_live, block_users=math.gcd(bu, 32),
        interpret=interpret,
    )
    return choice[:n], x[:n, :d]
