"""Substrate tests: optimizer, checkpointing (fault tolerance), MoE dispatch."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import moe, transformer
from repro.train import checkpoint, optimizer

KEY = jax.random.PRNGKey(0)


# --- optimizer -------------------------------------------------------------------


def test_adamw_first_step_is_scaled_sign():
    params = {"w": jnp.ones((4,))}
    grads = {"w": jnp.array([1.0, -1.0, 2.0, 0.0])}
    opt = optimizer.adamw_init(params)
    new, opt = optimizer.adamw_update(grads, opt, params, lr=0.1,
                                      weight_decay=0.0)
    # first Adam step with bias correction = lr * sign(g) (approximately)
    np.testing.assert_allclose(new["w"][:3], 1.0 - 0.1 * jnp.sign(
        grads["w"][:3]), rtol=1e-4)
    np.testing.assert_allclose(new["w"][3], 1.0)
    assert int(opt.step) == 1


def test_adamw_chunked_matches_unchunked():
    big = {"w": jax.random.normal(KEY, (4, 256, 400))}   # > threshold? no
    grads = {"w": jax.random.normal(jax.random.PRNGKey(1), (4, 256, 400))}
    opt = optimizer.adamw_init(big)
    ref, _ = optimizer.adamw_update(grads, opt, big)
    old = optimizer._CHUNK_BYTES
    try:
        optimizer._CHUNK_BYTES = 1024       # force chunking
        got, _ = optimizer.adamw_update(grads, opt, big)
    finally:
        optimizer._CHUNK_BYTES = old
    np.testing.assert_allclose(got["w"], ref["w"], rtol=1e-6, atol=1e-6)


def test_adafactor_decreases_loss():
    w_true = jnp.array([[1.0, -2.0], [0.5, 3.0]])
    params = {"w": jnp.zeros((2, 2))}
    opt = optimizer.adafactor_init(params, momentum_dtype=jnp.float32)
    x = jax.random.normal(KEY, (64, 2))

    def loss_fn(p):
        return jnp.mean((x @ p["w"] - x @ w_true) ** 2)

    losses = []
    for _ in range(60):
        loss, g = jax.value_and_grad(loss_fn)(params)
        params, opt = optimizer.adafactor_update(g, opt, params, lr=0.05)
        losses.append(float(loss))
    assert losses[-1] < 0.2 * losses[0]


def test_adagrad_sparse_accumulates():
    params = {"e": jnp.ones((8, 2))}
    g = {"e": jnp.zeros((8, 2)).at[3].set(1.0)}
    opt = optimizer.adagrad_init(params)
    new, opt = optimizer.adagrad_update(g, opt, params, lr=0.1)
    assert float(new["e"][3, 0]) < 1.0
    np.testing.assert_allclose(new["e"][0], 1.0)


# --- checkpoint / fault tolerance ---------------------------------------------


def _state(seed=0):
    k = jax.random.PRNGKey(seed)
    return {
        "params": {"w": jax.random.normal(k, (8, 4)),
                   "ln": [jnp.ones((4,)), jnp.zeros((4,))]},
        "step": jnp.int32(7),
        "occ": jnp.arange(8, dtype=jnp.int32),
    }


def test_checkpoint_roundtrip(tmp_path):
    mgr = checkpoint.CheckpointManager(tmp_path, keep=2)
    state = _state()
    mgr.save(state, 100)
    restored, step = mgr.restore_latest(jax.eval_shape(lambda: state))
    assert step == 100
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_keep_k_and_latest(tmp_path):
    mgr = checkpoint.CheckpointManager(tmp_path, keep=2)
    for s in (1, 2, 3, 4):
        mgr.save(_state(s), s)
    assert mgr.steps() == [3, 4]
    assert mgr.latest_step() == 4


def test_checkpoint_crash_leaves_no_corruption(tmp_path):
    """A tmp dir from a dead writer must not be visible as a checkpoint."""
    mgr = checkpoint.CheckpointManager(tmp_path, keep=3)
    mgr.save(_state(), 5)
    (tmp_path / "tmp-6").mkdir()                      # simulated dead writer
    (tmp_path / "tmp-6" / "arrays.npz").write_bytes(b"garbage")
    assert mgr.latest_step() == 5
    restored, step = mgr.restore_latest(jax.eval_shape(lambda: _state()))
    assert step == 5


def test_checkpoint_elastic_restore_changes_sharding(tmp_path):
    """Restore onto a different 'mesh' (1-device) — elastic scaling."""
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1,), ("x",))
    from jax.sharding import NamedSharding, PartitionSpec as P

    mgr = checkpoint.CheckpointManager(tmp_path)
    state = _state()
    mgr.save(state, 1)
    sh = jax.tree.map(lambda _: NamedSharding(mesh, P()), state)
    restored, _ = mgr.restore_latest(jax.eval_shape(lambda: state), sh)
    assert restored["params"]["w"].sharding == NamedSharding(mesh, P())


def test_checkpoint_resume_continues_training(tmp_path):
    """Simulated failure mid-run: resume reproduces the uninterrupted run."""
    from repro.core import distclub, env, env_ops
    from repro.core.types import BanditHyper

    e, _ = env.make_synthetic_env(KEY, 32, 8, 4, 10)
    ops = env_ops.synthetic_ops(e)
    hyper = BanditHyper(sigma=4, max_rounds=8, n_candidates=10)

    state = distclub.init_state(32, 8, hyper)
    keys = jax.random.split(jax.random.PRNGKey(9), 4)

    def epoch(state, k):
        k1, k3 = jax.random.split(k)
        state, _ = distclub.stage1(state, ops, k1, hyper)
        state = distclub.stage2(state, hyper, 8)
        state, _ = distclub.stage3(state, ops, k3, hyper)
        return distclub.stage4(state, hyper)

    # uninterrupted
    s_ref = state
    for k in keys:
        s_ref = epoch(s_ref, k)

    # interrupted after 2 epochs + restore
    mgr = checkpoint.CheckpointManager(tmp_path)
    s = state
    for k in keys[:2]:
        s = epoch(s, k)
    mgr.save(s, 2)
    restored, step = mgr.restore_latest(jax.eval_shape(lambda: s))
    assert step == 2
    for k in keys[2:]:
        restored = epoch(restored, k)

    np.testing.assert_allclose(np.asarray(s_ref.lin.b),
                               np.asarray(restored.lin.b), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_array_equal(np.asarray(s_ref.graph.labels),
                                  np.asarray(restored.graph.labels))


# --- MoE dispatch ---------------------------------------------------------------


def _moe_cfg(**kw):
    base = dict(n_layers=2, d_model=32, n_heads=2, n_kv_heads=2, d_head=16,
                d_ff=64, vocab=128, n_experts=4, top_k=2, n_shared=0,
                d_ff_expert=32, capacity_factor=4.0, dtype=jnp.float32)
    base.update(kw)
    return transformer.LMConfig(**base)


def test_moe_matches_dense_routing_at_high_capacity():
    """cf high enough -> no drops -> output == explicit per-token mixture."""
    cfg = _moe_cfg()
    params = moe.init_moe(KEY, cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 8, 32))
    out, aux = moe.moe_fwd(params, cfg, x)

    xt = x.reshape(-1, 32)
    logits = xt @ params["router"]
    probs = jax.nn.softmax(logits, -1)
    gv, gi = jax.lax.top_k(probs, cfg.top_k)
    gv = gv / gv.sum(-1, keepdims=True)
    we = params["experts"]

    def expert(e, z):
        h = jax.nn.silu(z @ we["gate"][e]) * (z @ we["up"][e])
        return h @ we["down"][e]

    want = jnp.zeros_like(xt)
    for t in range(xt.shape[0]):
        for j in range(cfg.top_k):
            want = want.at[t].add(gv[t, j] * expert(gi[t, j], xt[t]))
    np.testing.assert_allclose(out.reshape(-1, 32), want, rtol=2e-4,
                               atol=2e-4)
    assert float(aux) > 0


def test_moe_capacity_drops_tokens():
    cfg = _moe_cfg(capacity_factor=0.05, top_k=1)
    params = moe.init_moe(KEY, cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 16, 32))
    out, _ = moe.moe_fwd(params, cfg, x)
    # capacity 0.05 -> most tokens dropped -> many zero outputs
    zero_rows = jnp.sum(jnp.all(out.reshape(-1, 32) == 0, axis=-1))
    assert int(zero_rows) > 16


def test_moe_grads_flow_to_all_parts():
    cfg = _moe_cfg()
    params = moe.init_moe(KEY, cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 8, 32))

    def loss(p):
        out, aux = moe.moe_fwd(p, cfg, x)
        return jnp.sum(out ** 2) + 0.01 * aux

    g = jax.grad(loss)(params)
    assert float(jnp.abs(g["router"]).sum()) > 0
    assert float(jnp.abs(g["experts"]["gate"]).sum()) > 0


@pytest.mark.parametrize("env_dir", [None, "from-env"])
def test_compile_cache_dir(monkeypatch, tmp_path, env_dir):
    """The persistent compile cache follows JAX_COMPILATION_CACHE_DIR when
    it is set (and sets nothing else); otherwise it sits at the fixed
    <checkout>/.jax_cache, whatever the working directory."""
    import pathlib
    from repro.launch import compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.chdir(tmp_path)
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = str(pathlib.Path(__file__).resolve().parents[1] / ".jax_cache")
    else:
        want = str(tmp_path / env_dir)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
    try:
        assert compile_cache.enable() == want
        if env_dir is None:
            assert jax.config.jax_compilation_cache_dir == want
        else:
            assert jax.config.jax_compilation_cache_dir == before
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
