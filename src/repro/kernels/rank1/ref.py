"""Pure-jnp oracle for the fused masked rank-1 bandit-state update."""
from __future__ import annotations

import jax.numpy as jnp


def rank1_update_inv_ref(
    Minv: jnp.ndarray,    # [n, d, d]
    b: jnp.ndarray,       # [n, d]
    x: jnp.ndarray,       # [n, d]
    r: jnp.ndarray,       # [n]
    mask: jnp.ndarray,    # [n] bool
):
    """M-free oracle: (Minv', b') only (the sharded runtime's state).

    ``Minv`` may be stored bf16 (``Precision``): the S-M math runs in f32
    and the result is written back in the storage dtype.  For f32 both
    astypes are trace-time no-ops — bit-identical to the historical path.
    """
    dt = Minv.dtype
    Minv32 = Minv.astype(jnp.float32)
    m = mask.astype(x.dtype)
    xm = x * m[:, None]
    Mx = jnp.sum(Minv32 * xm[:, None, :], axis=-1)     # elementwise: exact
    denom = 1.0 + jnp.sum(xm * Mx, axis=-1)            # f32 at any precision
    Minv_new = Minv32 - jnp.einsum("ni,nj->nij", Mx, Mx) / denom[:, None,
                                                                 None]
    b_new = b + (r * m)[:, None] * x
    return Minv_new.astype(dt), b_new
