"""Fused interaction engine: choose-kernel parity, backend dispatch, and
end-to-end reference-vs-pallas agreement of the DistCLUB drivers.

All Pallas runs use interpret=True (this container has no TPU); the same
code path compiles on TPU with interpret=False.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

jax.config.update("jax_default_matmul_precision", "highest")

from repro.core import backend, distclub, env, env_ops, linucb
from repro.core.types import BanditHyper
from repro.kernels.interact import ops as interact_ops
from repro.kernels.rank1 import ops as rank1_ops
from repro.kernels.rank1.ref import rank1_update_inv_ref


def spd(key, n, d, scale=0.1):
    A = jax.random.normal(key, (n, d, d)) * scale
    return jnp.eye(d) + jnp.einsum("nij,nkj->nik", A, A)


# Ragged shapes on purpose: n not a block/sublane multiple, d not a sublane
# multiple, K not a lane multiple — all exercise the padding path.
@pytest.mark.parametrize("n,K,d", [
    (8, 16, 8),        # aligned n/d, ragged K
    (37, 20, 25),      # everything ragged
    (64, 7, 19),       # tiny ragged K
    (128, 128, 32),    # fully lane/sublane aligned (short-circuit path)
])
def test_fused_choose_matches_choose_batch(n, K, d):
    key = jax.random.PRNGKey(n * 1000 + K)
    ks = jax.random.split(key, 4)
    w = jax.random.normal(ks[0], (n, d))
    Minv = spd(ks[1], n, d)
    ctx = jax.random.normal(ks[2], (n, K, d))
    occ = jax.random.randint(ks[3], (n,), 0, 1000)

    choice_ref = linucb.choose_batch(w, Minv, ctx, occ, 0.3)
    x_ref = jnp.take_along_axis(ctx, choice_ref[:, None, None], axis=1)[:, 0]
    choice, x = interact_ops.choose(w, Minv, ctx, occ, 0.3,
                                    use_pallas=True, interpret=True)
    np.testing.assert_array_equal(np.asarray(choice), np.asarray(choice_ref))
    np.testing.assert_allclose(x, x_ref, rtol=2e-5, atol=2e-5)


def test_fused_choose_tie_breaks_like_argmax():
    """Duplicate candidates produce exactly equal scores; both paths must
    take the first index (jnp.argmax semantics)."""
    n, K, d = 16, 12, 8
    ctx = jax.random.normal(jax.random.PRNGKey(0), (n, K, d))
    ctx = ctx.at[:, 5].set(ctx[:, 2])       # k=5 duplicates k=2
    ctx = ctx.at[:, 9].set(ctx[:, 2])       # and k=9
    w = jax.random.normal(jax.random.PRNGKey(1), (n, d))
    Minv = jnp.broadcast_to(jnp.eye(d), (n, d, d))
    occ = jnp.ones((n,), jnp.int32)

    choice_ref = linucb.choose_batch(w, Minv, ctx, occ, 0.3)
    choice, _ = interact_ops.choose(w, Minv, ctx, occ, 0.3,
                                    use_pallas=True, interpret=True)
    np.testing.assert_array_equal(np.asarray(choice), np.asarray(choice_ref))
    assert not np.any(np.asarray(choice) == 5)
    assert not np.any(np.asarray(choice) == 9)


def test_fused_choose_padded_candidates_never_win():
    """All real scores negative: a zero-padded candidate (score 0) would win
    if the kernel failed to mask K-padding to -inf."""
    n, K, d = 8, 5, 4                       # K pads 5 -> 128
    ctx = -jnp.abs(jax.random.normal(jax.random.PRNGKey(2), (n, K, d)))
    w = jnp.ones((n, d))                    # est = sum(ctx) < 0
    Minv = jnp.zeros((n, d, d))             # no bonus term
    occ = jnp.zeros((n,), jnp.int32)
    choice, x = interact_ops.choose(w, Minv, ctx, occ, 0.5,
                                    use_pallas=True, interpret=True)
    assert np.asarray(choice).max() < K
    x_ref = jnp.take_along_axis(
        ctx, jnp.asarray(choice)[:, None, None], axis=1)[:, 0]
    np.testing.assert_allclose(x, x_ref, atol=1e-6)


@pytest.mark.parametrize("n,d", [(8, 8), (37, 25), (64, 32), (100, 19),
                                 (256, 32)])
def test_rank1_inv_kernel(n, d):
    key = jax.random.PRNGKey(n + d)
    ks = jax.random.split(key, 5)
    Minv = jnp.linalg.inv(spd(ks[0], n, d))
    b = jax.random.normal(ks[1], (n, d))
    x = jax.random.normal(ks[2], (n, d))
    r = jax.random.uniform(ks[3], (n,))
    mask = jax.random.bernoulli(ks[4], 0.7, (n,))
    refs = rank1_update_inv_ref(Minv, b, x, r, mask)
    outs = rank1_ops.rank1_update_inv(Minv, b, x, r, mask,
                                      use_pallas=True, interpret=True)
    for out, ref in zip(outs, refs):
        np.testing.assert_allclose(out, ref, rtol=3e-5, atol=3e-5)


def test_backend_dispatch_and_env_flag(monkeypatch):
    monkeypatch.delenv("REPRO_BACKEND", raising=False)
    be = backend.BackendConfig.create().interact(24, 5, 10)  # auto -> ref
    assert be.kind == "reference"
    assert (be.n_pad, be.d_pad, be.K_pad) == (24, 5, 10)  # no padding

    monkeypatch.setenv("REPRO_BACKEND", "pallas")
    be = backend.BackendConfig.create().interact(24, 5, 10)
    assert be.kind == "pallas" and be.interpret
    assert be.n_pad % be.block_users == 0
    assert be.d_pad % 8 == 0 and be.K_pad % 128 == 0

    monkeypatch.setenv("REPRO_BACKEND", "bogus")
    with pytest.raises(ValueError):
        backend.BackendConfig.create().interact(24, 5, 10)


def test_backend_pad_helpers_are_exact():
    be = backend.BackendConfig.create("pallas").interact(24, 5, 10,
                                                         interpret=True)
    lin = linucb.init_linucb(24, 5)
    Minv = be.pad_gram(lin.Minv)
    assert Minv.shape == (be.n_pad, be.d_pad, be.d_pad)
    # padded Gram blocks are identity (well-conditioned), real block intact
    np.testing.assert_allclose(
        Minv, jnp.broadcast_to(jnp.eye(be.d_pad),
                               (be.n_pad, be.d_pad, be.d_pad)))
    b = be.pad_vec(lin.b)
    occ = be.pad_users(lin.occ)
    assert b.shape == (be.n_pad, be.d_pad) and occ.shape == (be.n_pad,)
    back = (be.unpad_gram(Minv), be.unpad_vec(b), be.unpad_users(occ))
    for a, b_ in zip(back, (lin.Minv, lin.b, lin.occ)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b_))


def test_distclub_run_reference_vs_pallas_interpret():
    """Acceptance: fused path matches the reference path end to end —
    identical choices (hence identical realized rewards) and state arrays
    within atol 1e-5 — on a ragged shape that keeps padding live."""
    N, D, K = 24, 5, 10
    hyper = BanditHyper(sigma=4, max_rounds=8, gamma=1.5, n_candidates=K)
    e, _ = env.make_synthetic_env(jax.random.PRNGKey(0), N, D, 3, K)
    ops = env_ops.synthetic_ops(e)
    ref = backend.BackendConfig.create("reference").interact(N, D, K)
    pal = backend.BackendConfig.create("pallas").interact(N, D, K,
                                                          interpret=True)

    s_r, m_r, c_r = distclub.run(ops, jax.random.PRNGKey(1), hyper,
                                 n_epochs=2, d=D, backend=ref)
    s_p, m_p, c_p = distclub.run(ops, jax.random.PRNGKey(1), hyper,
                                 n_epochs=2, d=D, backend=pal)
    np.testing.assert_allclose(s_p.lin.M, s_r.lin.M, atol=1e-5)
    np.testing.assert_allclose(s_p.lin.Minv, s_r.lin.Minv, atol=1e-5)
    np.testing.assert_allclose(s_p.lin.b, s_r.lin.b, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(s_p.lin.occ),
                                  np.asarray(s_r.lin.occ))
    # same choices => same Bernoulli draws => identical realized rewards
    np.testing.assert_allclose(m_p.reward, m_r.reward, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(c_p), np.asarray(c_r))
