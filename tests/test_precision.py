"""The unified `Precision` backend policy (README "Precision policies").

Covers the API redesign's acceptance criteria:
  * `Precision.f32` — the default — is bit-identical to the pre-policy
    behavior: explicit-f32 sessions match default sessions choice for
    choice and state bit for bit, across reference/pallas engines and
    single-host/8-device sharded serving;
  * bf16/int8 sessions bound the per-decision choice-flip rate vs the
    f32 oracle on seeded traffic (counterfactual probes on the oracle's
    own trajectory — occ/b stay exact, flips come only from the score
    contraction), at test scale and at 256 users x 4,096 items;
  * int8 per-slot dequant scales survive catalog churn: staged
    retire/add, double-buffered publish, and slot reclaim keep every
    untouched slot's codes+scale bit-identical and give churn-added rows
    fresh per-row scales with the quantization error bound intact;
  * checkpoints record the precision policy and `restore` fails loudly
    on a mismatch — a reduced-precision snapshot is not silently
    reinterpretable;
  * cluster-pruned retrieval stays EXACT under quantized tile summaries
    (conservative dequantized bounds — `core.itemclub`);
  * the deprecated backend factories (`get_backend` & co.) still serve
    the same engines as the `BackendConfig` API that replaced them.
"""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import serve
from repro.core import catalog as catalog_mod
from repro.core import env
from repro.core.backend import (BackendConfig, Precision, get_backend,
                                get_graph_backend, get_retrieval_backend,
                                resolve_precision)
from repro.core.types import BanditHyper
from repro.train.checkpoint import CheckpointManager

from test_distributed import _run_with_devices

D, KS = 16, 16
N_USERS, N_ITEMS, B = 64, 512, 32
HYPER = BanditHyper(alpha=0.05, gamma=1.5, n_candidates=KS)


def _world(seed=7):
    k = jax.random.normal(jax.random.PRNGKey(seed), (N_ITEMS, D))
    emb = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    th = jax.random.normal(jax.random.PRNGKey(seed + 1), (N_USERS, D))
    theta = th / jnp.linalg.norm(th, axis=-1, keepdims=True)
    return emb, theta


def _session(precision=None, backend="reference", interpret=None):
    return serve.OnlineBandit.create(N_USERS, D, HYPER, policy="distclub",
                                     refresh_every=0, backend=backend,
                                     interpret=interpret,
                                     precision=precision)


def _uids(t):
    return jax.random.permutation(jax.random.PRNGKey(100 + t),
                                  N_USERS)[:B].astype(jnp.int32)


def _reward_fn(theta):
    def reward_fn(key, u, ctx, choice):
        return env.step_rewards(key, theta[u], ctx, choice)
    return reward_fn


# ---------------------------------------------------------------------------
# f32 bit-identity
# ---------------------------------------------------------------------------

def test_f32_policy_is_bit_identical_to_default():
    """Explicit `precision="f32"` is the default policy: same compiled
    transaction, bit-equal choices and state."""
    emb, theta = _world()
    rf = _reward_fn(theta)
    s_def, s_f32 = _session(None), _session("f32")
    cat = serve.make_catalog(emb)
    cat_f32 = serve.make_catalog(emb, precision="f32")
    assert s_f32.policy.cfg.engine.precision == Precision.f32
    assert s_f32.state.Minv.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(cat.emb),
                                  np.asarray(cat_f32.emb))
    for t in range(3):
        k, u = jax.random.PRNGKey(1000 + t), _uids(t)
        s_def, c1, _ = serve.step_catalog(s_def, k, u, cat, rf, k_short=KS)
        s_f32, c2, _ = serve.step_catalog(s_f32, k, u, cat_f32, rf,
                                          k_short=KS)
        np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))
    np.testing.assert_array_equal(np.asarray(s_def.state.Minv),
                                  np.asarray(s_f32.state.Minv))
    np.testing.assert_array_equal(np.asarray(s_def.state.b),
                                  np.asarray(s_f32.state.b))


def test_f32_reference_vs_pallas_engines_identical():
    """The f32 policy through the pallas(-interpret) engine serves the
    reference engine's choices bit for bit."""
    emb, theta = _world()
    rf = _reward_fn(theta)
    sr = _session("f32", backend="reference")
    sp = _session("f32", backend="pallas", interpret=True)
    cat = serve.make_catalog(emb, precision="f32")
    for t in range(2):
        k, u = jax.random.PRNGKey(1000 + t), _uids(t)
        sr, cr, _ = serve.step_catalog(sr, k, u, cat, rf, k_short=KS)
        sp, cp, _ = serve.step_catalog(sp, k, u, cat, rf, k_short=KS)
        np.testing.assert_array_equal(np.asarray(cr), np.asarray(cp))
    np.testing.assert_allclose(np.asarray(sr.state.Minv),
                               np.asarray(sp.state.Minv), atol=1e-5)


def test_f32_sharded_8dev_matches_single_host():
    out = _run_with_devices("""
        import numpy as np
        import jax, jax.numpy as jnp
        from repro import serve
        from repro.core import env
        from repro.core.types import BanditHyper

        N, D, KS, B, NI = 64, 16, 16, 32, 512
        hyper = BanditHyper(alpha=0.05, gamma=1.5, n_candidates=KS)
        k = jax.random.normal(jax.random.PRNGKey(7), (NI, D))
        emb = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
        th = jax.random.normal(jax.random.PRNGKey(8), (N, D))
        theta = th / jnp.linalg.norm(th, axis=-1, keepdims=True)

        def reward_fn(key, u, ctx, choice):
            return env.step_rewards(key, theta[u], ctx, choice)

        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("users",))
        s1 = serve.OnlineBandit.create(N, D, hyper, policy="distclub",
                                       refresh_every=0,
                                       backend="reference",
                                       precision="f32")
        s8 = serve.OnlineBandit.sharded(mesh, N, D, hyper,
                                        policy="distclub",
                                        refresh_every=0,
                                        backend="reference",
                                        precision="f32")
        cat = serve.make_catalog(emb, precision="f32")
        from repro.core import catalog as catalog_mod
        from repro.distributed.distclub_shard import named_shardings
        cat8 = jax.device_put(cat, named_shardings(
            mesh, catalog_mod.specs(("users",))))
        for t in range(3):
            key = jax.random.PRNGKey(1000 + t)
            u = jax.random.permutation(jax.random.PRNGKey(100 + t),
                                       N)[:B].astype(jnp.int32)
            s1, c1, _ = serve.step_catalog(s1, key, u, cat, reward_fn,
                                           k_short=KS)
            s8, c8, _ = serve.step_catalog(s8, key, u, cat8, reward_fn,
                                           k_short=KS)
            np.testing.assert_array_equal(np.asarray(c1), np.asarray(c8))
        np.testing.assert_array_equal(np.asarray(s1.state.occ),
                                      np.asarray(s8.state.occ))
        np.testing.assert_allclose(np.asarray(s1.state.Minv),
                                   np.asarray(s8.state.Minv), atol=1e-6)
        print("PRECISION-SHARD-OK")
    """)
    assert "PRECISION-SHARD-OK" in out


# ---------------------------------------------------------------------------
# reduced-precision flip-rate bound
# ---------------------------------------------------------------------------

def _flip_counts(emb, theta, session, uids, warm, meas, ks, batch):
    """Counterfactual choice flips of bf16 and int8 against the f32
    oracle's own trajectory: each measured round, the oracle's decision is
    compared with the decision from the SAME state cast to the reduced
    state dtype, against the reduced catalog.  Returns (flips, total)."""
    rf = _reward_fn(theta)
    oracle = session(None)
    cat = serve.make_catalog(emb)
    probes = {p: (session(p), serve.make_catalog(emb, precision=p))
              for p in ("bf16", "int8")}
    flips = {p: 0 for p in probes}
    total = 0
    for t in range(warm + meas):
        u = uids(t)
        if t >= warm:
            idf, _, _ = serve.recommend_catalog(oracle, u, cat, k_short=ks)
            total += batch
            for p, (rs, catp) in probes.items():
                sdt = rs.policy.cfg.engine.precision.jnp_state
                st = oracle.state._replace(
                    Minv=oracle.state.Minv.astype(sdt),
                    uMcinv=oracle.state.uMcinv.astype(sdt))
                idr, _, _ = serve.recommend_catalog(
                    dataclasses.replace(rs, state=st), u, catp, k_short=ks)
                flips[p] += int(jnp.sum(idf != idr))
        oracle, _, _ = serve.step_catalog(oracle,
                                          jax.random.PRNGKey(1000 + t), u,
                                          cat, rf, k_short=ks)
    return flips, total


def test_bf16_int8_choice_flip_rate_bounded():
    """Counterfactual per-decision probes on the f32 oracle's trajectory
    (``_flip_counts`` at test scale): after the cold-start warmup,
    bf16/int8 flip at most 2% of choices — and the reduced sessions
    really store reduced state."""
    emb, theta = _world()
    for p in ("bf16", "int8"):
        assert _session(p).state.Minv.dtype == jnp.bfloat16
    assert serve.make_catalog(emb, precision="int8").serving.emb.dtype \
        == jnp.int8
    flips, total = _flip_counts(emb, theta, _session, _uids, 20, 8, KS, B)
    for p, f in flips.items():
        assert f / total <= 0.02, (p, f, total)


@pytest.fixture(scope="module")
def full_size_flips():
    """bf16/int8 flips at 256 users, 4,096 distinct items, d = 32, a
    64-item shortlist and 64 requests a round: 32 warm-up rounds, then 40
    measured (2,560 decisions)."""
    d, ks, n_users, n_items, batch = 32, 64, 256, 4096, 64
    k = jax.random.normal(jax.random.PRNGKey(7), (n_items, d))
    emb = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    th = jax.random.normal(jax.random.PRNGKey(8), (n_users, d))
    theta = th / jnp.linalg.norm(th, axis=-1, keepdims=True)
    hyper = BanditHyper(alpha=0.05, gamma=1.5, n_candidates=ks)

    def session(precision):
        return serve.OnlineBandit.create(n_users, d, hyper,
                                         policy="distclub", refresh_every=0,
                                         backend="reference",
                                         precision=precision)

    def uids(t):
        return jax.random.permutation(jax.random.PRNGKey(100 + t),
                                      n_users)[:batch].astype(jnp.int32)

    return _flip_counts(emb, theta, session, uids, 32, 40, ks, batch)


# ceilings: 110% of the seeded flip rates recorded at this size
@pytest.mark.parametrize("prec,recorded", [("bf16", 0.0015625),
                                           ("int8", 0.001953125)])
def test_choice_flip_rate_holds_at_full_size(full_size_flips, prec,
                                             recorded):
    flips, total = full_size_flips
    assert total == 2560
    assert flips[prec] / total <= 1.1 * recorded, (prec, flips[prec])


# ---------------------------------------------------------------------------
# int8 scale round-trip through churn / publish / reclaim
# ---------------------------------------------------------------------------

def test_int8_scales_survive_churn_publish_and_reclaim():
    prec = Precision(state_dtype="bf16", catalog_dtype="int8",
                     scale_block=64)
    emb, _ = _world()
    cat = serve.make_catalog(emb, capacity=N_ITEMS + 32, precision=prec)
    assert cat.serving.emb.dtype == jnp.int8
    # initial quantization honors the error bound: one shared scale per
    # 64-slot block, |dequant - orig| <= scale/2 per component
    deq = np.asarray(catalog_mod.dequantize(cat.serving))
    orig = np.zeros_like(deq)
    orig[:N_ITEMS] = np.asarray(emb)
    sc = np.asarray(cat.serving.scale)
    assert np.all(np.abs(deq - orig) <= sc[:, None] / 2 + 1e-7)

    # stage churn: retire a block-straddling id range, add replacements
    retired = jnp.arange(10, 20, dtype=jnp.int32)
    cat1, n_ret = catalog_mod.retire_items(cat, retired)
    new_rows = 3.0 * jax.random.normal(jax.random.PRNGKey(5), (6, D))
    cat1, slots, n_add = catalog_mod.add_items(cat1, new_rows)
    assert int(n_ret) == 10 and int(n_add) == 6
    before = cat1.serving
    cat2 = catalog_mod.publish(cat1)
    after = cat2.serving

    # untouched slots: codes AND scales bit-identical across the swap
    touched = np.zeros(cat.capacity, bool)
    touched[np.asarray(retired)] = True
    touched[np.asarray(slots)] = True
    np.testing.assert_array_equal(np.asarray(before.emb)[~touched],
                                  np.asarray(after.emb)[~touched])
    np.testing.assert_array_equal(np.asarray(before.scale)[~touched],
                                  np.asarray(after.scale)[~touched])

    # churn-added rows got fresh PER-ROW scales (maxabs/127 — these rows
    # are far outside the initial blocks' range) and still dequantize
    # within the bound; the spare-capacity tail slots were claimed first
    got = np.asarray(slots)
    nr = np.asarray(new_rows)
    for i, s in enumerate(got):
        want_scale = max(np.abs(nr[i]).max(), 1e-8) / 127.0
        assert np.isclose(float(after.scale[s]), want_scale, rtol=1e-6)
        row = np.asarray(catalog_mod.dequantize(after))[s]
        assert np.all(np.abs(row - nr[i]) <= want_scale / 2 + 1e-6)

    # reclaim: a retired slot is reusable — the NEXT add claims it and
    # overwrites its scale with the new row's own
    cat3, slots2, _ = catalog_mod.add_items(
        cat2, 0.5 * jax.random.normal(jax.random.PRNGKey(6), (4, D)))
    assert set(np.asarray(slots2).tolist()) <= set(range(10, 20))
    cat3 = catalog_mod.publish(cat3)
    s0 = int(np.asarray(slots2)[0])
    assert float(cat3.serving.scale[s0]) != float(cat2.serving.scale[s0])
    # and a full no-churn publish round-trip is a bit-exact identity on
    # the serving bank
    cat4 = catalog_mod.publish(catalog_mod.publish(cat3))
    np.testing.assert_array_equal(np.asarray(cat3.serving.emb),
                                  np.asarray(cat4.serving.emb))
    np.testing.assert_array_equal(np.asarray(cat3.serving.scale),
                                  np.asarray(cat4.serving.scale))


# ---------------------------------------------------------------------------
# checkpoint precision tag
# ---------------------------------------------------------------------------

def test_checkpoint_precision_mismatch_raises(tmp_path):
    emb, theta = _world()
    rf = _reward_fn(theta)
    s16 = _session("bf16")
    cat = serve.make_catalog(emb, precision="bf16")
    s16, _, _ = serve.step_catalog(s16, jax.random.PRNGKey(0), _uids(0),
                                   cat, rf, k_short=KS)
    ck = CheckpointManager(tmp_path / "prec", keep=2)
    s16.save(ck, step=1)

    # same precision: round-trips bit-exactly, reduced dtypes intact
    s16b, got_step = _session("bf16").restore(ck, step=1)
    assert got_step == 1
    assert s16b.state.Minv.dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(s16.state.Minv.astype(jnp.float32)),
        np.asarray(s16b.state.Minv.astype(jnp.float32)))

    # different precision: loud refusal, not silent reinterpretation
    with pytest.raises(ValueError, match="precision mismatch"):
        _session("f32").restore(ck, step=1)
    with pytest.raises(ValueError, match="precision mismatch"):
        _session("int8").restore(ck, step=1)


# ---------------------------------------------------------------------------
# pruned retrieval stays exact under quantized tile summaries
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("prec", ["bf16", "int8"])
def test_pruned_retrieval_exact_under_quantized_summaries(prec):
    """Cluster-pruned shortlists must never drop a true member: the
    quantized tile summaries widen conservatively (`core.itemclub`), so
    the pruned serve is bit-identical to unpruned — while still
    actually skipping tiles on a region-structured catalog."""
    e, _ = env.make_catalog_env(jax.random.PRNGKey(0), N_USERS, D, 4,
                                N_ITEMS, item_noise_scale=0.05)
    emb = env.catalog_embeddings(e)
    rf = _reward_fn(e.theta)
    sess = _session(prec)
    cat = serve.make_catalog(emb, precision=prec)
    for t in range(4):
        sess, _, _ = serve.step_catalog(sess, jax.random.PRNGKey(2000 + t),
                                        _uids(t), cat, rf, k_short=KS)
    cl = serve.build_clusters(cat, tile_items=64, n_anchors=64)
    u = jnp.arange(B, dtype=jnp.int32)
    ids_plain, _, _ = serve.recommend_catalog(sess, u, cat, k_short=KS)
    ids_pruned, _, _, rmet = serve.recommend_catalog(sess, u, cat,
                                                     k_short=KS,
                                                     clusters=cl)
    np.testing.assert_array_equal(np.asarray(ids_plain),
                                  np.asarray(ids_pruned))
    assert float(rmet.skip_ratio()) > 0.0


# ---------------------------------------------------------------------------
# deprecated factories still serve the BackendConfig engines
# ---------------------------------------------------------------------------

def test_deprecated_factories_match_backendconfig():
    """`get_backend`/`get_graph_backend`/`get_retrieval_backend` remain
    importable working aliases of the unified `BackendConfig` API (old
    call sites keep running while they migrate)."""
    eng_old = get_backend(N_USERS, D, KS, "reference")
    eng_new = BackendConfig(kind="reference",
                            precision=resolve_precision(None)).interact(
                                N_USERS, D, KS)
    assert eng_old == eng_new

    gb_old = get_graph_backend(N_USERS, kind="reference")
    gb_new = BackendConfig(kind="reference",
                           precision=resolve_precision(None)).graph(N_USERS)
    assert gb_old == gb_new

    rb_old = get_retrieval_backend(D, KS, "reference")
    rb_new = BackendConfig(kind="reference",
                           precision=resolve_precision(None)).retrieval(
                               D, KS)
    assert rb_old == rb_new
