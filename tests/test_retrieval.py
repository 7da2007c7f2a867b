"""Catalog-scale retrieval: streaming top-K engine + two-stage serving.

Covers the PR-5 acceptance criteria:
  * top-K kernel parity vs the reference oracle at ragged shapes
    (N_items not a tile multiple, d off the sublane multiple, retired
    items masked) — identical ids, identical scores;
  * deterministic (score desc, id asc) selection: all-tied fresh state
    shortlists the lowest live ids;
  * two-stage recommend == direct-slate choose BIT-IDENTICALLY when the
    catalog fits in one slate (N_items <= K);
  * 8-device item-sharded shortlist + serving transaction == single-host
    (subprocess mesh, the ``tests/test_parity.py`` pattern);
  * save/restore round-trip of a serving session together with its
    Catalog through ``CheckpointManager``;
  * the ``kind="catalog"`` offline environment: shard-invariant draws
    and a learnable planted signal.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import serve
from repro.core import catalog as catalog_mod
from repro.core import env, env_ops
from repro.core.backend import BackendConfig
from repro.core.types import BanditHyper
from repro.data import datasets
from repro.train.checkpoint import CheckpointManager

from test_distributed import _run_with_devices

D = 8
HYPER = BanditHyper(sigma=4, max_rounds=1, gamma=1.5, n_candidates=10)


def _spd_stats(key, n, d, scale=0.1):
    ks = jax.random.split(key, 3)
    w = jax.random.normal(ks[0], (n, d))
    A = scale * jax.random.normal(ks[1], (n, d, d))
    Minv = jnp.eye(d) + jnp.einsum("nab,ncb->nac", A, A)
    occ = jax.random.randint(ks[2], (n,), 0, 50)
    return w, Minv, occ


# ---------------------------------------------------------------------------
# kernel parity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n,d,N,Ks", [
    (10, 7, 70, 8),       # everything ragged: n, d, N off every multiple
    (16, 8, 64, 16),      # aligned
    (5, 12, 260, 4),      # N just over a tile at block_items=128
])
def test_topk_pallas_matches_reference_ragged(n, d, N, Ks):
    """Reference oracle vs interpret-mode Pallas kernel: identical ids
    AND scores at ragged shapes with retired items in the mix — tiling
    and padding cannot perturb the (score, id) selection."""
    w, Minv, occ = _spd_stats(jax.random.PRNGKey(0), n, d)
    ks = jax.random.split(jax.random.PRNGKey(1), 2)
    items = jax.random.normal(ks[0], (N, d))
    items = items / jnp.linalg.norm(items, axis=-1, keepdims=True)
    live = (jax.random.uniform(ks[1], (N,)) > 0.25).astype(jnp.float32)

    r_ref = BackendConfig.create("reference").retrieval(
        d, Ks, row_block=4, item_block=16)
    r_pal = BackendConfig.create("pallas").retrieval(
        d, Ks, block_users=8, block_items=32, interpret=True)
    s1, i1 = r_ref.shortlist(w, Minv, occ, items, live, 0.3)
    s2, i2 = r_pal.shortlist(w, Minv, occ, items, live, 0.3)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=1e-6)

    # matches a dense brute-force top-K with (score desc, id asc) order
    scores = (jnp.einsum("nd,Nd->nN", w, items)
              + 0.3 * jnp.sqrt(jnp.maximum(jnp.einsum(
                  "Na,nab,Nb->nN", items, Minv, items), 0.0))
              * jnp.sqrt(jnp.log1p(occ.astype(jnp.float32)))[:, None])
    scores = jnp.where(live[None, :] > 0, scores, -jnp.inf)
    order = jnp.lexsort((jnp.broadcast_to(jnp.arange(N)[None], (n, N)),
                         -scores), axis=-1)[:, :Ks]
    want = jnp.where(jnp.isfinite(
        jnp.take_along_axis(scores, order, axis=1)), order, -1)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(want))

    # retired items never surface
    dead = set(np.nonzero(np.asarray(live) == 0)[0].tolist())
    assert not (set(np.asarray(i1).ravel().tolist()) & dead)


def test_topk_all_tied_prefers_lowest_live_ids():
    """Fresh statistics score every item identically (w=0, occ=0 kills
    the bonus): the shortlist must be the lowest LIVE ids in order —
    the tie-break that makes two-stage == direct-slate exact."""
    n, d, N, Ks = 4, 8, 40, 6
    items = jax.random.normal(jax.random.PRNGKey(0), (N, d))
    items = items / jnp.linalg.norm(items, axis=-1, keepdims=True)
    live = jnp.ones((N,), jnp.float32).at[jnp.array([0, 2, 3])].set(0.0)
    for kind, kw in [("reference", dict(row_block=4, item_block=16)),
                     ("pallas", dict(block_users=8, block_items=16,
                                     interpret=True))]:
        rb = BackendConfig.create(kind).retrieval(d, Ks, **kw)
        _, ids = rb.shortlist(jnp.zeros((n, d)),
                              jnp.broadcast_to(jnp.eye(d), (n, d, d)),
                              jnp.zeros((n,), jnp.int32), items, live, 0.3)
        want = np.array([1, 4, 5, 6, 7, 8])
        np.testing.assert_array_equal(np.asarray(ids),
                                      np.broadcast_to(want, (n, Ks)))


def test_topk_underfull_catalog_pads_with_minus_one():
    """k_short > live items: the tail keeps score -inf / id -1."""
    n, d, N, Ks = 3, 4, 5, 8
    items = jnp.eye(N, d, dtype=jnp.float32)
    live = jnp.ones((N,), jnp.float32).at[4].set(0.0)
    rb = BackendConfig.create("reference").retrieval(d, Ks, row_block=2,
                                                     item_block=4)
    w, Minv, occ = _spd_stats(jax.random.PRNGKey(2), n, d)
    s, i = rb.shortlist(w, Minv, occ, items, live, 0.3)
    assert (np.asarray(i)[:, 4:] == -1).all()
    assert not np.isfinite(np.asarray(s)[:, 4:]).any()
    assert (np.asarray(i)[:, :4] >= 0).all()


def test_shortlist_row0_offsets_ids():
    """row0_items turns tile-local ids global (the item-sharded path)."""
    n, d, N, Ks = 4, 8, 32, 4
    w, Minv, occ = _spd_stats(jax.random.PRNGKey(3), n, d)
    items = jax.random.normal(jax.random.PRNGKey(4), (N, d))
    live = jnp.ones((N,), jnp.float32)
    rb = BackendConfig.create("reference").retrieval(d, Ks)
    _, i0 = rb.shortlist(w, Minv, occ, items, live, 0.3)
    _, i7 = rb.shortlist(w, Minv, occ, items, live, 0.3, row0_items=7 * N)
    np.testing.assert_array_equal(np.asarray(i7), np.asarray(i0) + 7 * N)


# ---------------------------------------------------------------------------
# catalog state
# ---------------------------------------------------------------------------


def test_catalog_add_retire_roundtrip():
    cat = catalog_mod.random_catalog(jax.random.PRNGKey(0), 6, D,
                                     capacity=10)
    assert int(cat.n_live()) == 6
    cat, n_ret = catalog_mod.retire_items(cat,
                                          jnp.array([1, 4, -1], jnp.int32))
    assert int(n_ret) == 2
    # STAGED only: serving is untouched until the epoch flip
    assert int(cat.n_live()) == 6 and int(cat.epoch) == 0
    cat = catalog_mod.publish(cat)
    assert int(cat.n_live()) == 4 and int(cat.epoch) == 1
    fresh = jnp.ones((3, D), jnp.float32)
    cat, slots, n_add = catalog_mod.add_items(cat, fresh)
    # lowest dead slots first: the two just-retired + the first spare
    np.testing.assert_array_equal(np.asarray(slots), [1, 4, 6])
    assert int(n_add) == 3
    assert int(cat.n_live()) == 4           # still the published view
    cat = catalog_mod.publish(cat)
    assert int(cat.n_live()) == 7 and int(cat.epoch) == 2
    np.testing.assert_array_equal(np.asarray(cat.serving.emb[slots]),
                                  np.asarray(fresh))
    # arrivals are stamped with the epoch their publish created
    np.testing.assert_array_equal(np.asarray(cat.serving.born[slots]),
                                  [2, 2, 2])


# ---------------------------------------------------------------------------
# two-stage serving
# ---------------------------------------------------------------------------


def _catalog_world(n_users=16, n_items=6, n_candidates=None, seed=0):
    e, _ = env.make_catalog_env(
        jax.random.PRNGKey(seed), n_users, D, 4, n_items,
        n_candidates=n_candidates or HYPER.n_candidates)
    return e, serve.make_catalog(env.catalog_embeddings(e))


def _theta_reward_fn(theta):
    def reward_fn(key, uids, ctx, choice):
        return env.step_rewards(key, theta[uids], ctx, choice)
    return reward_fn


def test_two_stage_equals_direct_slate_bit_identical():
    """N_items <= K: the shortlist is the whole catalog in (score desc,
    id asc) order, so shortlist -> fused choose returns the exact item
    the direct-slate path picks — fresh (all-tied) AND trained state."""
    n_users, n_items = 16, 6
    hyper = HYPER._replace(n_candidates=n_items)
    e, cat = _catalog_world(n_users, n_items, n_candidates=n_items)
    reward_fn = _theta_reward_fn(e.theta)
    uids = jnp.arange(n_users, dtype=jnp.int32)
    slate = jnp.broadcast_to(env.catalog_embeddings(e)[None],
                             (n_users, n_items, D))

    sess = serve.OnlineBandit.create(n_users, D, hyper, policy="distclub")
    for i in range(6):            # i=0 probes the all-tied fresh state
        direct = serve.recommend(sess, uids, slate)   # slate idx == item id
        two_stage, _, _ = serve.recommend_catalog(sess, uids, cat,
                                                  k_short=16)
        np.testing.assert_array_equal(np.asarray(direct),
                                      np.asarray(two_stage))
        sess, items, _ = serve.step_catalog(sess, jax.random.PRNGKey(i),
                                            uids, cat, reward_fn,
                                            k_short=16)
        np.testing.assert_array_equal(np.asarray(items),
                                      np.asarray(direct))


def test_step_catalog_folds_feedback_and_learns():
    """The full transaction learns the planted signal: realized reward
    beats uniform-random-over-the-CATALOG (the metrics' own rand_reward
    is random-over-the-shortlist — already top-UCB items, so the honest
    retrieval baseline is the full catalog), occ advances, retired items
    vanish."""
    n_users, n_items = 32, 128
    e, cat = _catalog_world(n_users, n_items)
    retired = jnp.array([5, 50, 77], jnp.int32)
    cat, _ = serve.retire_items(cat, retired)
    cat = serve.publish(cat)
    reward_fn = _theta_reward_fn(e.theta)
    uids = jnp.arange(n_users, dtype=jnp.int32)
    # a FIXED catalog needs real exploration pressure (fresh-slate tests
    # resample contexts every round; here the 128 arms never change, so
    # the paper's alpha=0.03 parks everyone on one early item)
    hyper = HYPER._replace(alpha=0.5)
    sess = serve.OnlineBandit.create(n_users, D, hyper, policy="distclub",
                                     refresh_every=2 * n_users)
    steps, tot_r = 25, 0.0
    seen_items = set()
    for i in range(steps):
        sess, items, m = serve.step_catalog(
            sess, jax.random.PRNGKey(i), uids, cat, reward_fn, k_short=8)
        tot_r += float(m.reward)
        seen_items |= set(np.asarray(items).tolist())
    assert int(sess.state.occ.sum()) == steps * n_users
    assert not seen_items & set(np.asarray(retired).tolist())
    # uniform-random catalog baseline: mean expected reward of a live item
    p = 0.5 * (1.0 + e.theta @ env.catalog_embeddings(e).T)   # [n, N]
    p_rand = jnp.sum(p * cat.serving.live[None, :n_items],
                     axis=1) / jnp.sum(cat.serving.live[:n_items])
    baseline = steps * float(jnp.sum(p_rand))
    assert tot_r > baseline * 1.1, (tot_r, baseline)


def test_recommend_catalog_observe_matches_step_catalog():
    """The split request/feedback halves land on the same state as the
    fused catalog transaction when fed the realized rewards."""
    n_users, n_items = 16, 64
    e, cat = _catalog_world(n_users, n_items)
    reward_fn = _theta_reward_fn(e.theta)
    uids = jnp.arange(n_users, dtype=jnp.int32)
    sess_a = sess_b = serve.OnlineBandit.create(n_users, D, HYPER,
                                                policy="distclub")
    for i in range(3):
        key = jax.random.PRNGKey(i)
        sess_a, items_a, _ = serve.step_catalog(sess_a, key, uids, cat,
                                                reward_fn, k_short=8)
        items_b, slots, ctx = serve.recommend_catalog(sess_b, uids, cat,
                                                      k_short=8)
        np.testing.assert_array_equal(np.asarray(items_a),
                                      np.asarray(items_b))
        realized, _, _, _ = reward_fn(key, uids, ctx, slots)
        sess_b = serve.observe(sess_b, uids, ctx, slots, realized, key=key)
    np.testing.assert_array_equal(np.asarray(sess_a.state.occ),
                                  np.asarray(sess_b.state.occ))
    np.testing.assert_allclose(np.asarray(sess_a.state.Minv),
                               np.asarray(sess_b.state.Minv), atol=1e-6)


def test_item_sharded_8dev_matches_single_host():
    """Item-sharded two-stage serving == single-host, bit for bit: the
    per-shard shortlists merge to the identical global shortlist, the
    replicated choose picks the identical item, the feedback fold lands
    on the identical state (subprocess 8-device mesh)."""
    out = _run_with_devices("""
        import numpy as np
        import jax, jax.numpy as jnp
        from repro import serve
        from repro.core import catalog as catalog_mod, env
        from repro.core.types import BanditHyper
        from repro.distributed.distclub_shard import named_shardings

        N_USERS, D, N_ITEMS, KS = 64, 8, 256, 16
        hyper = BanditHyper(sigma=4, max_rounds=1, gamma=1.5,
                            n_candidates=10)
        e, _ = env.make_catalog_env(jax.random.PRNGKey(0), N_USERS, D, 4,
                                    N_ITEMS, n_candidates=10)
        cat = serve.make_catalog(env.catalog_embeddings(e))
        cat, _ = serve.retire_items(cat, jnp.array([3, 17, 200], jnp.int32))
        cat = serve.publish(cat)
        theta = e.theta

        def reward_fn(key, uids, ctx, choice):
            return env.step_rewards(key, theta[uids], ctx, choice)

        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("users",))
        s1 = serve.OnlineBandit.create(N_USERS, D, hyper,
                                       policy="distclub",
                                       refresh_every=2 * N_USERS)
        s8 = serve.OnlineBandit.sharded(mesh, N_USERS, D, hyper,
                                        policy="distclub",
                                        refresh_every=2 * N_USERS)
        cat8 = jax.device_put(
            cat, named_shardings(mesh, catalog_mod.specs(("users",))))
        for i in range(5):
            k = jax.random.PRNGKey(i)
            uids = jax.random.permutation(
                jax.random.PRNGKey(100 + i), N_USERS).astype(jnp.int32)
            s1, i1, m1 = serve.step_catalog(s1, k, uids, cat, reward_fn,
                                            k_short=KS)
            s8, i8, m8 = serve.step_catalog(s8, k, uids, cat8, reward_fn,
                                            k_short=KS)
            np.testing.assert_array_equal(np.asarray(i1), np.asarray(i8))
            assert float(m1.reward) == float(m8.reward)
        assert not set(np.asarray(i1).tolist()) & {3, 17, 200}
        # a refresh fired inside the jitted transaction by now
        assert int(s1.state.since_refresh) == int(s8.state.since_refresh)
        np.testing.assert_array_equal(np.asarray(s1.state.occ),
                                      np.asarray(s8.state.occ))
        np.testing.assert_array_equal(np.asarray(s1.state.labels),
                                      np.asarray(s8.state.labels))
        np.testing.assert_allclose(np.asarray(s1.state.Minv),
                                   np.asarray(s8.state.Minv), atol=1e-6)
        print("ITEM-SHARD-PARITY-OK")
    """)
    assert "ITEM-SHARD-PARITY-OK" in out


def test_catalog_session_checkpoint_roundtrip(tmp_path):
    """A serving session WITH its catalog round-trips through
    CheckpointManager: the restored pair resumes with bit-identical
    recommendations (catalog liveness churn included)."""
    n_users, n_items = 16, 64
    e, cat = _catalog_world(n_users, n_items)
    cat, _ = serve.retire_items(cat, jnp.array([9, 30], jnp.int32))
    cat = serve.publish(cat)
    reward_fn = _theta_reward_fn(e.theta)
    uids = jnp.arange(n_users, dtype=jnp.int32)
    sess = serve.OnlineBandit.create(n_users, D, HYPER, policy="distclub",
                                     refresh_every=n_users)
    for i in range(3):
        sess, _, _ = serve.step_catalog(sess, jax.random.PRNGKey(i), uids,
                                        cat, reward_fn, k_short=8)
    ck = CheckpointManager(tmp_path / "cat-sess", keep=2)
    ck.save((sess.state, cat), 3)

    cont_items, cont = [], sess
    for i in range(3, 6):
        cont, items, _ = serve.step_catalog(cont, jax.random.PRNGKey(i),
                                            uids, cat, reward_fn,
                                            k_short=8)
        cont_items.append(np.asarray(items))

    fresh = serve.OnlineBandit.create(n_users, D, HYPER, policy="distclub",
                                      refresh_every=n_users)
    fresh_cat = serve.make_catalog(jnp.zeros((n_items, D), jnp.float32))
    (state, cat_r), step = ck.restore_latest((fresh.state, fresh_cat))
    assert step == 3
    restored = fresh.__class__(policy=fresh.policy, state=state)
    np.testing.assert_array_equal(np.asarray(cat_r.live),
                                  np.asarray(cat.live))
    for i, want in zip(range(3, 6), cont_items):
        restored, items, _ = serve.step_catalog(
            restored, jax.random.PRNGKey(i), uids, cat_r, reward_fn,
            k_short=8)
        np.testing.assert_array_equal(np.asarray(items), want)
    np.testing.assert_array_equal(np.asarray(restored.state.occ),
                                  np.asarray(cont.state.occ))


# ---------------------------------------------------------------------------
# the kind="catalog" offline environment
# ---------------------------------------------------------------------------


def test_catalog_env_ops_shard_invariant_draws():
    """Slates drawn from the persistent catalog are keyed per GLOBAL
    user id: a row0 slice sees exactly the full-range rows (the sharding
    parity contract every EnvOps obeys)."""
    spec = datasets.DatasetSpec("t", 1024, 16, D, 4, n_candidates=5)
    ops, _ = datasets.make_env(spec, kind="catalog", n_items=32)
    key = jax.random.PRNGKey(0)
    occ = jnp.zeros((16,), jnp.int32)
    full = ops.contexts_fn(key, occ, 0)
    half = ops.contexts_fn(key, occ[8:], 8)
    np.testing.assert_array_equal(np.asarray(full[8:]), np.asarray(half))
    r_full = ops.rewards_fn(key, occ, full, jnp.zeros((16,), jnp.int32), 0)
    r_half = ops.rewards_fn(key, occ[8:], half,
                            jnp.zeros((8,), jnp.int32), 8)
    np.testing.assert_array_equal(np.asarray(r_full[0][8:]),
                                  np.asarray(r_half[0]))


def test_catalog_env_drift_redraws_regions():
    """With drift_period set, crossing the phase boundary re-draws the
    region centroids: the same (user, key) slate changes; within a phase
    it is stable."""
    e, _ = env.make_catalog_env(jax.random.PRNGKey(0), 8, D, 2, 64,
                                n_candidates=4, drift_period=10,
                                n_phases=3)
    ops = env_ops.catalog_ops(e)
    key = jax.random.PRNGKey(5)
    occ0 = jnp.zeros((8,), jnp.int32)
    a = ops.contexts_fn(key, occ0, 0)
    b = ops.contexts_fn(key, occ0 + 5, 0)       # same phase
    c = ops.contexts_fn(key, occ0 + 10, 0)      # next phase
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert not np.allclose(np.asarray(a), np.asarray(c))
    # the phase-0 table equals the materialized serving catalog rows
    ids = jax.vmap(lambda k: jax.random.randint(k, (4,), 0, 64))(
        jax.vmap(jax.random.fold_in, in_axes=(None, 0))(
            key, jnp.arange(8, dtype=jnp.int32)))
    np.testing.assert_allclose(np.asarray(a),
                               np.asarray(env.catalog_embeddings(e)[ids]),
                               atol=1e-6)


# ---------------------------------------------------------------------------
# churn edge cases + degenerate serving batches (PR-6 regressions)
# ---------------------------------------------------------------------------


def test_add_items_partial_fill_beyond_capacity():
    """Adding past free capacity places what fits (ascending dead slots,
    input order) and returns slot -1 for the overflow — live embeddings
    are never overwritten."""
    cat = catalog_mod.random_catalog(jax.random.PRNGKey(1), 6, D,
                                     capacity=8)
    before = np.asarray(cat.serving.emb[:6]).copy()
    fresh = jnp.arange(5 * D, dtype=jnp.float32).reshape(5, D)
    cat2, slots, n_add = catalog_mod.add_items(cat, fresh)
    np.testing.assert_array_equal(np.asarray(slots), [6, 7, -1, -1, -1])
    assert int(n_add) == 2
    cat2 = catalog_mod.publish(cat2)
    assert int(cat2.n_live()) == 8
    np.testing.assert_array_equal(np.asarray(cat2.serving.emb[:6]), before)
    np.testing.assert_array_equal(np.asarray(cat2.serving.emb[6:]),
                                  np.asarray(fresh[:2]))
    # a full catalog accepts nothing, even a batch wider than capacity
    cat3, slots3, n3 = catalog_mod.add_items(
        cat2, jnp.ones((12, D), jnp.float32))
    assert int(n3) == 0
    assert np.all(np.asarray(slots3) == -1)
    np.testing.assert_array_equal(
        np.asarray(catalog_mod.publish(cat3).serving.emb),
        np.asarray(cat2.serving.emb))


def test_retire_items_dead_dup_out_of_range_are_noops():
    """Retiring dead slots, duplicates, negatives, and out-of-range ids
    is a counted no-op — only real live->dead transitions count."""
    cat = catalog_mod.random_catalog(jax.random.PRNGKey(2), 4, D,
                                     capacity=6)
    cat, n1 = catalog_mod.retire_items(
        cat, jnp.array([2, 2, 5, -3, 99], jnp.int32))
    assert int(n1) == 1                 # only slot 2 was live
    cat = catalog_mod.publish(cat)
    assert int(cat.n_live()) == 3
    cat, n2 = catalog_mod.retire_items(cat, jnp.array([2, 5], jnp.int32))
    assert int(n2) == 0                 # both already dead
    assert int(catalog_mod.publish(cat).n_live()) == 3
    # retire-then-readd stages back onto the freed slot (same shadow
    # bank, so the staged retirement and the add compose)
    cat, slots, n3 = catalog_mod.add_items(cat,
                                           jnp.ones((1, D), jnp.float32))
    assert int(n3) == 1 and np.asarray(slots).tolist() == [2]


def _degenerate_world(n_users=16, n_items=64):
    e, _ = env.make_catalog_env(jax.random.PRNGKey(4), n_users, D, 4,
                                n_items, n_candidates=HYPER.n_candidates)
    cat = serve.make_catalog(env.catalog_embeddings(e))

    def reward_fn(key, uids, ctx, choice):
        return env.step_rewards(key, e.theta[uids], ctx, choice)
    return e, cat, reward_fn


_DEGENERATE_REWARD_FNS = {}


def _degenerate_cached(n_users=16, n_items=64):
    # reward_fn identity keys the compiled transaction; cache per shape
    key = (n_users, n_items)
    if key not in _DEGENERATE_REWARD_FNS:
        _DEGENERATE_REWARD_FNS[key] = _degenerate_world(n_users, n_items)
    return _DEGENERATE_REWARD_FNS[key]


def test_step_catalog_all_padded_batch_is_noop():
    """Every uid < 0: no items served (-1), zero interactions, state
    byte-identical — the degenerate batch a sharded pipeline's tail
    produces."""
    _, cat, reward_fn = _degenerate_cached()
    sess = serve.OnlineBandit.create(16, D, HYPER, policy="distclub",
                                     refresh_every=64)
    uids = jnp.full((8,), -1, jnp.int32)
    sess2, items, m = serve.step_catalog(sess, jax.random.PRNGKey(0),
                                         uids, cat, reward_fn, k_short=8)
    assert np.all(np.asarray(items) == -1)
    assert int(m.interactions) == 0
    assert float(m.reward) == 0.0
    for a, b in zip(jax.tree_util.tree_leaves(sess.state),
                    jax.tree_util.tree_leaves(sess2.state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_step_catalog_underfull_shortlist_tiny_live_count():
    """k_short > live items: the shortlist pads with the user's top
    entry, served items stay within the live set, feedback folds."""
    _, cat, reward_fn = _degenerate_cached()
    keep = jnp.array([7, 21], jnp.int32)
    dead = jnp.array([i for i in range(64) if i not in (7, 21)],
                     jnp.int32)
    cat, n_ret = serve.retire_items(cat, dead)
    cat = serve.publish(cat)
    assert int(n_ret) == 62 and int(cat.n_live()) == 2
    sess = serve.OnlineBandit.create(16, D, HYPER, policy="distclub",
                                     refresh_every=64)
    uids = jnp.arange(8, dtype=jnp.int32)
    for i in range(3):
        sess, items, m = serve.step_catalog(sess, jax.random.PRNGKey(i),
                                            uids, cat, reward_fn,
                                            k_short=8)
        assert set(np.asarray(items).tolist()) <= set(
            np.asarray(keep).tolist()), items
        assert int(m.interactions) == 8
    assert int(jnp.sum(sess.state.occ)) == 24


def test_step_catalog_duplicate_uids_interleaved_with_padding():
    """[u, -1, u, -1, v]: both occurrences of u fold (occurrence-rank
    passes), padding contributes nothing."""
    _, cat, reward_fn = _degenerate_cached()
    sess = serve.OnlineBandit.create(16, D, HYPER, policy="distclub",
                                     refresh_every=1000)
    uids = jnp.array([3, -1, 3, -1, 5], jnp.int32)
    sess2, items, m = serve.step_catalog(sess, jax.random.PRNGKey(0),
                                         uids, cat, reward_fn, k_short=8)
    assert int(m.interactions) == 3
    assert int(sess2.state.occ[3]) == 2
    assert int(sess2.state.occ[5]) == 1
    it = np.asarray(items)
    assert it[1] == -1 and it[3] == -1
    assert it[0] >= 0 and it[2] >= 0 and it[4] >= 0
