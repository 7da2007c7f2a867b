"""Public entry point for the batched SPD inverse."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..pad import LANE, SUB, round_up
from .ref import spd_inverse_ref
from .spdinv import spd_inverse_pallas

# The kernel's blocks (in and out, double-buffered) and its residual
# scratch are kept to three quarters of the 16 MiB of VMEM a v5e kernel
# is granted by default.
VMEM_BUDGET = 12 * 2 ** 20


def spd_block(n: int, d: int) -> int | None:
    """Users per kernel block: the largest multiple of 128 whose five
    ``[d_pad, d_pad, Bu]`` f32 buffers fit ``VMEM_BUDGET``, no larger
    than the padded batch; ``None`` where not even 128 users fit."""
    dp = round_up(d, SUB)
    fit = VMEM_BUDGET // (5 * 4 * dp * dp) // LANE * LANE
    if fit == 0:
        return None
    return min(fit, round_up(n, LANE))


def spd_inverse(
    A: jnp.ndarray,
    *,
    use_pallas: bool | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Inverses of a batch ``A [..., d, d]`` of symmetric positive
    definite matrices.

    Gauss-Jordan elimination WITHOUT pivoting and one step of iterative
    refinement, in the input's dtype (f32 on the chip): it is stable
    only where every leading principal minor stays well away from zero,
    which SPD matrices guarantee.  Do not hand it a matrix that is not
    SPD.  An identity matrix comes back exactly, and so does the padding
    the kernel adds (identity users and identity rows/columns beyond
    ``d``, all dropped afterwards).

    On TPU (``use_pallas=None``) the Pallas kernel runs, unless ``d`` is
    too wide for a 128-user block; elsewhere the jnp reference does.
    """
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    d = A.shape[-1]
    a = A.reshape(-1, d, d)
    n = a.shape[0]
    bu = spd_block(n, d)
    if not use_pallas or bu is None:
        out = spd_inverse_ref(jnp.transpose(a, (1, 2, 0)))
        return jnp.transpose(out, (2, 0, 1)).reshape(A.shape)

    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    dp, n_pad = round_up(d, SUB), round_up(n, bu)
    t = jnp.pad(jnp.transpose(a, (1, 2, 0)),
                ((0, dp - d), (0, dp - d), (0, n_pad - n)))
    i = jax.lax.broadcasted_iota(jnp.int32, t.shape, 0)
    j = jax.lax.broadcasted_iota(jnp.int32, t.shape, 1)
    u = jax.lax.broadcasted_iota(jnp.int32, t.shape, 2)
    real = (i < d) & (j < d) & (u < n)
    t = jnp.where(real, t, (i == j).astype(t.dtype))
    out = spd_inverse_pallas(t, d=d, block_users=bu, interpret=interpret)
    return jnp.transpose(out[:d, :d, :n], (2, 0, 1)).reshape(A.shape)
