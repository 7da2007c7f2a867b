"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs pure-jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

jax.config.update("jax_default_matmul_precision", "highest")

from repro.kernels.cross import ops as cross_ops
from repro.kernels.cross.ref import cross_layer_ref
from repro.kernels.embag import ops as embag_ops
from repro.kernels.embag.ref import embedding_bag_ref
from repro.kernels.flash import ops as flash_ops
from repro.kernels.flash.ref import mha_ref
from repro.kernels.rank1 import ops as rank1_ops


def spd(key, n, d, scale=0.1):
    A = jax.random.normal(key, (n, d, d)) * scale
    return jnp.eye(d) + jnp.einsum("nij,nkj->nik", A, A)


# d = 19 is MovieLens-25M's genre width, d = 25 the paper's synthetic width
@pytest.mark.parametrize("d", [8, 12, 19, 25, 32])
def test_rank1_sherman_morrison_is_exact_inverse(d):
    """The kernel's Minv' inverts the explicit M + x x^T."""
    key = jax.random.PRNGKey(7)
    n = 16
    M = spd(key, n, d)
    Minv = jnp.linalg.inv(M)
    x = jax.random.normal(key, (n, d))
    r = jnp.ones((n,))
    mask = jnp.ones((n,), bool)
    Minv2, _ = rank1_ops.rank1_update_inv(
        Minv, jnp.zeros((n, d)), x, r, mask, use_pallas=True, interpret=True
    )
    M2 = M + jnp.einsum("ni,nj->nij", x, x)
    np.testing.assert_allclose(
        jnp.einsum("nij,njk->nik", Minv2, M2),
        jnp.broadcast_to(jnp.eye(d), (n, d, d)), atol=1e-3,
    )


@pytest.mark.parametrize("V,D,B,L", [(50, 8, 4, 3), (1000, 64, 16, 10), (128, 128, 8, 1)])
def test_embag_kernel(V, D, B, L):
    key = jax.random.PRNGKey(V + B)
    ks = jax.random.split(key, 3)
    table = jax.random.normal(ks[0], (V, D))
    idx = jax.random.randint(ks[1], (B, L), 0, V)
    wt = jax.random.uniform(ks[2], (B, L))
    ref = embedding_bag_ref(table, idx, wt)
    out = embag_ops.embedding_bag(table, idx, wt, use_pallas=True,
                                  interpret=True)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


def test_embag_pad_slots_are_zero_weight():
    table = jnp.arange(20, dtype=jnp.float32).reshape(10, 2)
    idx = jnp.array([[1, 2, 0]])
    wt = jnp.array([[1.0, 1.0, 0.0]])   # pad slot points at row 0, weight 0
    out = embag_ops.embedding_bag(table, idx, wt, use_pallas=True,
                                  interpret=True)
    np.testing.assert_allclose(out[0], table[1] + table[2])


@pytest.mark.parametrize("B,d", [(16, 16), (37, 24), (100, 64)])
def test_cross_kernel(B, d):
    key = jax.random.PRNGKey(B + d)
    ks = jax.random.split(key, 4)
    x0 = jax.random.normal(ks[0], (B, d))
    xl = jax.random.normal(ks[1], (B, d))
    W = jax.random.normal(ks[2], (d, d)) / jnp.sqrt(d)
    bias = jax.random.normal(ks[3], (d,))
    np.testing.assert_allclose(
        cross_ops.cross_layer(x0, xl, W, bias, use_pallas=True, interpret=True),
        cross_layer_ref(x0, xl, W, bias), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("B,Hq,Hkv,Sq,Skv,Dh,causal,off", [
    (1, 2, 2, 128, 128, 64, True, 0),      # MHA causal
    (2, 4, 2, 256, 256, 64, True, 0),      # GQA causal
    (1, 8, 1, 128, 128, 32, False, 0),     # MQA bidirectional
    (2, 4, 4, 64, 256, 64, True, 192),     # chunked decode tail
])
def test_flash_kernel(B, Hq, Hkv, Sq, Skv, Dh, causal, off):
    key = jax.random.PRNGKey(Sq + Skv)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, Hq, Sq, Dh))
    k = jax.random.normal(ks[1], (B, Hkv, Skv, Dh))
    v = jax.random.normal(ks[2], (B, Hkv, Skv, Dh))
    out = flash_ops.attention(q, k, v, causal=causal, q_offset=off,
                              use_pallas=True, block_q=64, block_k=64,
                              interpret=True)
    ref = mha_ref(q, k, v, causal=causal, q_offset=off)
    np.testing.assert_allclose(out, ref, rtol=2e-3, atol=2e-3)


def test_flash_bf16():
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 3)
    B, H, S, Dh = 1, 2, 128, 64
    q = jax.random.normal(ks[0], (B, H, S, Dh), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, H, S, Dh), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, H, S, Dh), jnp.bfloat16)
    out = flash_ops.attention(q, k, v, causal=True, use_pallas=True,
                              block_q=64, block_k=64, interpret=True)
    ref = mha_ref(q.astype(jnp.float32), k.astype(jnp.float32),
                  v.astype(jnp.float32), causal=True)
    np.testing.assert_allclose(out.astype(jnp.float32), ref, rtol=5e-2,
                               atol=5e-2)
