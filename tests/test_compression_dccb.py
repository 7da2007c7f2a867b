"""Tests: int8 gradient compression w/ error feedback + sharded DCCB gossip."""
import jax
import jax.numpy as jnp
import numpy as np
from _hypothesis_shim import given, settings, st

from repro.distributed import compression
from test_distributed import _run_with_devices


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 5000), st.integers(0, 2**31 - 1))
def test_compress_roundtrip_bounded_error(n, seed):
    x = jax.random.normal(jax.random.PRNGKey(seed), (n,)) * 10
    c = compression.compress(x)
    y = compression.decompress(c, x.shape)
    # per-block max-scaled int8: error <= scale/2 = max|block|/254
    err = jnp.abs(y - x)
    assert float(err.max()) <= float(jnp.abs(x).max()) / 254 + 1e-6


def test_compression_ratio():
    r = compression.compressed_ratio((1024, 1024), jnp.float32)
    assert r < 0.27          # ~4x smaller than f32


def test_error_feedback_preserves_signal():
    """Sum of transported grads + final error == sum of true grads
    (error feedback never loses mass)."""
    key = jax.random.PRNGKey(0)
    params = {"w": jnp.zeros((257,))}       # non-multiple of block
    err = compression.init_error(params)
    total_true = jnp.zeros((257,))
    total_sent = jnp.zeros((257,))
    for i in range(5):
        g = {"w": jax.random.normal(jax.random.fold_in(key, i), (257,))}
        g_hat, err = compression.ef_step(g, err)
        total_true += g["w"]
        total_sent += g_hat["w"]
    np.testing.assert_allclose(
        np.asarray(total_sent + err["w"]), np.asarray(total_true),
        rtol=1e-5, atol=1e-5)


def test_sharded_dccb_runs_and_ships_buffers():
    out = _run_with_devices("""
        import jax
        from repro.distributed import dccb_shard
        from repro.core.types import BanditHyper

        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("users",))
        hyper = BanditHyper(alpha=0.05, gamma=1.5, n_candidates=10)
        n, d, L = 64, 8, 8
        init_fn, epoch = dccb_shard.make_runtime(
            mesh, ("users",), n, d, L, hyper)
        state = init_fn(jax.random.PRNGKey(0))
        tot_r = tot_rand = 0.0
        for i in range(6):
            state, m = epoch(state, jax.random.PRNGKey(i + 1))
            tot_r += float(m.reward.sum()); tot_rand += float(m.rand_reward.sum())
        comm = float(state.comm_bytes)
        want = 6 * n * (L + 1) * (d * d + d) * 4
        assert comm == want, (comm, want)
        assert tot_r > tot_rand * 0.98, (tot_r, tot_rand)
        print("DCCB-SHARD-OK", tot_r / tot_rand)
    """)
    assert "DCCB-SHARD-OK" in out
