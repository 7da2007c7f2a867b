"""The batched SPD inverse (``kernels/spdinv``): Gauss-Jordan, users on lanes.

Inputs are shaped like the program's: ``Minv`` after Sherman-Morrison
folds of unit contexts, its Gram ``M``, a large cluster sum ``Mc`` and a
label-indexed table whose unused rows are the identity.  The reference is
held to float64 within twice the error of f32 ``jnp.linalg.inv`` on the
same batch; the Pallas kernel (interpret mode) to the reference.  The
DistCLUB call is then traced: no LU is left in it, and the kernel runs
under every scope that inverted a batch.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend.core import Jaxpr

from repro.core import distclub, env, env_ops
from repro.core.backend import BackendConfig
from repro.core.types import BanditHyper
from repro.kernels.pad import SUB, round_up
from repro.kernels.spdinv.ops import spd_block, spd_inverse
from repro.kernels.spdinv.spdinv import spd_inverse_pallas

N = 300          # not a multiple of the 128-user lane block


def _grams(d: int, folds: int, seed: int):
    """(Minv f32 by Sherman-Morrison, M f64) after ``folds`` unit contexts."""
    rng = np.random.default_rng(seed)
    Minv = np.tile(np.eye(d, dtype=np.float32), (N, 1, 1))
    M = np.tile(np.eye(d), (N, 1, 1))
    for _ in range(folds):
        x = rng.standard_normal((N, d))
        x /= np.linalg.norm(x, axis=1, keepdims=True)
        x32 = x.astype(np.float32)
        mx = np.einsum("nij,nj->ni", Minv, x32)
        den = 1 + np.einsum("ni,ni->n", x32, mx)
        Minv = Minv - np.einsum("ni,nj->nij", mx, mx) / den[:, None, None]
        M += np.einsum("ni,nj->nij", x, x)
    return Minv, M


def _batch(kind: str, d: int) -> np.ndarray:
    Minv, M = _grams(d, folds=60, seed=d)
    eye = np.eye(d)
    if kind == "minv":
        return Minv
    if kind == "gram":
        return M.astype(np.float32)
    Mc = eye + 80 * (M - eye)            # a cluster of 80 such users
    if kind == "table":                  # rows that are no live label: I
        Mc[::3] = eye
    return Mc.astype(np.float32)


def _fro_err(X, ref) -> float:
    """Largest per-matrix relative error in the Frobenius norm."""
    X, ref = np.asarray(X, np.float64), np.asarray(ref, np.float64)
    diff = np.linalg.norm((X - ref).reshape(len(X), -1), axis=1)
    return float(np.max(diff / np.linalg.norm(ref.reshape(len(X), -1),
                                              axis=1)))


@pytest.mark.parametrize("d", [25, 32])
@pytest.mark.parametrize("kind", ["minv", "gram", "cluster", "table"])
def test_spd_inverse(kind, d):
    A = jnp.asarray(_batch(kind, d))
    exact = np.linalg.inv(np.asarray(A, np.float64))

    ref = spd_inverse(A, use_pallas=False)
    lu_err = _fro_err(jnp.linalg.inv(A), exact)
    assert _fro_err(ref, exact) <= 2 * lu_err

    out = spd_inverse(A, use_pallas=True, interpret=True)
    assert out.shape == A.shape and out.dtype == A.dtype
    assert _fro_err(out, ref) <= 1e-6
    if kind == "table":
        eye = np.broadcast_to(np.eye(d, dtype=np.float32), (N // 3, d, d))
        np.testing.assert_array_equal(np.asarray(ref)[::3], eye)
        np.testing.assert_array_equal(np.asarray(out)[::3], eye)


@pytest.mark.parametrize("d", [25, 32])
def test_spd_inverse_padding_comes_back_exactly(d):
    """Padded users and the rows/columns beyond ``d`` hold the identity
    going in, and the kernel hands them back bit for bit."""
    bu = spd_block(N, d)
    dp, n_pad = round_up(d, SUB), round_up(N, bu)
    assert n_pad > N
    A = _batch("cluster", d)
    t = np.broadcast_to(np.eye(dp, dtype=np.float32)[:, :, None],
                        (dp, dp, n_pad)).copy()
    t[:d, :d, :N] = A.transpose(1, 2, 0)
    out = np.asarray(spd_inverse_pallas(jnp.asarray(t), d=d, block_users=bu,
                                        interpret=True))
    pad = np.ones((dp, dp, n_pad), bool)
    pad[:d, :d, :N] = False
    np.testing.assert_array_equal(out[pad], t[pad])


def test_spd_block_follows_the_shape():
    assert spd_block(20480, 25) == 512       # 5 x 32*32*512 f32 = 10 MiB
    assert spd_block(300, 25) == 384         # no wider than the batch
    assert spd_block(20480, 64) == 128
    assert spd_block(20480, 72) is None      # the jnp path takes it


def _eqns(jaxpr, stack=()):
    """Every equation of a jaxpr and its sub-jaxprs, with its scope path."""
    for e in jaxpr.eqns:
        path = stack + tuple(str(e.source_info.name_stack).split("/"))
        yield e, path
        for p in e.params.values():
            for sub in (p if isinstance(p, (tuple, list)) else (p,)):
                inner = getattr(sub, "jaxpr", sub)
                if isinstance(inner, Jaxpr):
                    yield from _eqns(inner, path)


def test_distclub_run_inverts_with_the_kernel_at_every_site(monkeypatch):
    """``distclub._run`` at 256 users: no LU primitive is left, and the
    kernel is called under each scope that inverts a batch.  The chip's
    path is traced here by telling the op it runs on a TPU."""
    n, d, K = 256, 25, 20
    hyper = BanditHyper(sigma=8, max_rounds=16, gamma=1.5, n_candidates=K)
    e, _ = env.make_synthetic_env(jax.random.PRNGKey(0), n, d, 4, K)
    bc = BackendConfig.create("reference", "f32")
    be = bc.interact(n, d, K)
    gb = bc.graph(n, interpret=be.interpret)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    closed = jax.make_jaxpr(
        lambda key: distclub._run(env_ops.synthetic_ops(e), key, hyper, 2,
                                  d, be, gb))(jax.random.PRNGKey(1))
    eqns = list(_eqns(closed.jaxpr))
    assert not [e for e, _ in eqns if e.primitive.name.startswith("lu")]
    sites = {s for e, path in eqns if e.primitive.name == "pallas_call"
             and e.params["name"] == "spd_inverse"
             for s in path}
    assert {"init", "gram_inverse", "cluster_inverse", "refresh_gram"} <= sites
