"""Batched SPD inverse Pallas kernel: Gauss-Jordan with users on lanes.

``jnp.linalg.inv`` of an ``[n, d, d]`` batch lowers on TPU to an LU
custom call that factors one small matrix at a time.  Here the batch is
laid out ``[d, d, n]``: a block ``[d_pad, d_pad, Bu]`` of ``Bu`` users
is read into VMEM once, the ``d`` pivot steps and one refinement step
run there as VPU multiply-adds on ``[d_pad, Bu]`` rows (``Bu`` users
side by side on the lanes), and the block is written back once: one HBM
read and one HBM write per batch.

Grid: one step per block of users.  Pivot steps are unrolled (``k`` is
static, so the pivot column is a static sublane slice); the rows a step
eliminates are a loop over the block's leading axis.  Only the first
``d`` rows are touched: padded rows and columns hold the identity, which
every step leaves exactly as it is.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import eliminate, pivot_row


def _rows_times(x_ref, y_ref, i, d: int):
    """Row ``i`` of ``X Y`` for the block: ``sum_m X[i, m] Y[m]``."""
    x = x_ref[i]                                # [d_pad, Bu]
    acc = x[0:1] * y_ref[0]
    for m in range(1, d):
        acc = acc + x[m:m + 1] * y_ref[m]
    return acc


def _spdinv_kernel(a_ref, o_ref, e_ref, *, d: int):
    o_ref[...] = a_ref[...]
    for k in range(d):
        r = pivot_row(o_ref[k], k)              # [d_pad, Bu]

        def elim(i, carry, r=r, k=k):
            o_ref[i] = eliminate(o_ref[i], r, k)
            return carry

        jax.lax.fori_loop(0, d, elim, 0)
        o_ref[k] = r

    # one refinement step, X + X (I - A X): E = I - A X first, in full,
    # since every new row of X reads all of E.  Rows of E beyond d are
    # zero and never read, so the scratch holds only the first d.
    col = jax.lax.broadcasted_iota(jnp.int32, e_ref.shape[1:], 0)

    def residual(i, carry):
        e_ref[i] = (col == i).astype(jnp.float32) - _rows_times(
            a_ref, o_ref, i, d)
        return carry

    def refine(i, carry):
        o_ref[i] = o_ref[i] + _rows_times(o_ref, e_ref, i, d)
        return carry

    jax.lax.fori_loop(0, d, residual, 0)
    jax.lax.fori_loop(0, d, refine, 0)


@functools.partial(jax.jit, static_argnames=("d", "block_users",
                                             "interpret"))
def spd_inverse_pallas(
    A: jnp.ndarray,      # [d_pad, d_pad, n] f32, identity beyond d
    *,
    d: int,
    block_users: int,
    interpret: bool = False,
):
    dp, _, n = A.shape
    assert n % block_users == 0
    spec = pl.BlockSpec((dp, dp, block_users), lambda u: (0, 0, u))
    return pl.pallas_call(
        functools.partial(_spdinv_kernel, d=d),
        grid=(n // block_users,),
        in_specs=[spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(A.shape, A.dtype),
        scratch_shapes=[pltpu.VMEM((d, dp, block_users), jnp.float32)],
        interpret=interpret,
        name="spd_inverse",
    )(A)
