"""Fused masked Sherman-Morrison rank-1 update Pallas kernel.

Per interaction the bandit touches, per user: Minv (S-M downdate) and b
(+= r x).  The runtime carries no Gram matrix M (stage 2 recovers it by
inversion), so the hot loop reads each user's state once into VMEM,
applies both updates, and writes once: two state passes per round.

Grid: one step per block of users.  The per-user matvec ``Minv x`` is a
VPU multiply plus lane reduction over the ``[Bu, d, d]`` block (Mosaic
rejects the batched ``dot_general``), and the row copy of ``Minv x`` the
outer product needs is taken through an identity mask — exact, since it
adds zeros.  ``r`` and ``mask`` are ``[n, 1]`` columns: 1-D blocks do not
match XLA's TPU layout.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _sherman_morrison(minv, xm):
    """(M + x x')^-1 from M^-1 for a block: minv [Bu, d, d] f32, xm [Bu, d]."""
    d = minv.shape[-1]
    mx_col = jnp.sum(minv * xm[:, None, :], axis=2, keepdims=True)  # [Bu,d,1]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (1, d, d), 1)
           == jax.lax.broadcasted_iota(jnp.int32, (1, d, d), 2))
    mx_row = jnp.sum(jnp.where(eye, mx_col, 0.0), axis=1,
                     keepdims=True)                                   # [Bu,1,d]
    denom = 1.0 + jnp.sum(xm[:, None, :] * mx_row, axis=2, keepdims=True)
    return minv - mx_col * mx_row / denom


def _rank1_inv_kernel(minv_ref, b_ref, x_ref, r_ref, mask_ref,
                      minv_out, b_out):
    # Minv may arrive bf16 (Precision state_dtype): upcast once in VMEM so
    # the S-M math runs f32; for f32 inputs the astype is a no-op.
    x = x_ref[...]                 # [Bu, d]
    msk = mask_ref[...]            # [Bu, 1] (f32 0/1)
    xm = x * msk
    minv_out[...] = _sherman_morrison(
        minv_ref[...].astype(jnp.float32), xm).astype(minv_out.dtype)
    b_out[...] = b_ref[...] + r_ref[...] * msk * x


@functools.partial(jax.jit, static_argnames=("block_users", "interpret"))
def rank1_update_inv_pallas(
    Minv: jnp.ndarray,   # [n, d, d]
    b: jnp.ndarray,      # [n, d]
    x: jnp.ndarray,      # [n, d]
    r: jnp.ndarray,      # [n]
    mask: jnp.ndarray,   # [n] f32 (0/1)
    *,
    block_users: int = 64,
    interpret: bool = False,
):
    n, d = b.shape
    assert n % block_users == 0
    grid = (n // block_users,)
    bs2 = pl.BlockSpec((block_users, d, d), lambda i: (i, 0, 0))
    bs1 = pl.BlockSpec((block_users, d), lambda i: (i, 0))
    bs0 = pl.BlockSpec((block_users, 1), lambda i: (i, 0))
    return pl.pallas_call(
        _rank1_inv_kernel,
        grid=grid,
        in_specs=[bs2, bs1, bs1, bs0, bs0],
        out_specs=[bs2, bs1],
        out_shape=[
            jax.ShapeDtypeStruct((n, d, d), Minv.dtype),
            jax.ShapeDtypeStruct((n, d), jnp.float32),
        ],
        interpret=interpret,
        name="rank1_update_inv",
    )(Minv, b, x, r.reshape(n, 1), mask.reshape(n, 1))
