"""Cluster-pruned retrieval: item-side CLUB clustering + exact tile
pruning.

Covers the PR acceptance criteria:
  * the per-(user, tile) UCB bound DOMINATES every member item's score
    (the soundness that makes pruning exact);
  * pruned shortlist == unpruned shortlist BIT-EQUAL — reference and
    interpret-mode Pallas, on random, adversarial near-tie (repeated
    embeddings) and region-structured catalogs;
  * region recovery: the anchor CLUB graph + nearest-anchor assignment
    rediscovers the planted item regions, and the reference/pallas graph
    engines build the identical clustering;
  * churn safety: a `publish` the cluster table has not seen makes the
    serving transaction FALL BACK to the unpruned stream (same items,
    ``pruned_active == 0``), and `refresh_clusters` re-arms it; sustained
    churn keeps the layout a permutation with exact live accounting;
  * single-host vs 8-device item-sharded pruned serving bit-identical
    (subprocess mesh, the ``tests/test_retrieval.py`` pattern);
  * `ItemStats` feedback fold: duplicate-safe scatter, padding dropped,
    reclaimed slots reset;
  * `Guarded` telemetry: the skip ratio lands in ``ema_tiles_skipped``
    and the recall probe (vs the unpruned oracle) stays 1.0.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import serve
from repro.core import catalog as catalog_mod
from repro.core import env, itemclub
from repro.core.backend import BackendConfig
from repro.core.types import BanditHyper
from repro.kernels.topk import ops as topk_ops
from repro.kernels.topk.ref import (BOUND_SLACK, tile_bounds, topk_ref,
                                    topk_ref_pruned)
from repro.train.checkpoint import CheckpointManager

from test_distributed import _run_with_devices

HYPER = BanditHyper(alpha=0.3, sigma=4, max_rounds=1, gamma=1.5,
                    n_candidates=10)


def _stats(key, n, d, scale=0.1):
    ks = jax.random.split(key, 3)
    w = jax.random.normal(ks[0], (n, d))
    A = scale * jax.random.normal(ks[1], (n, d, d))
    Minv = jnp.eye(d) + jnp.einsum("nab,ncb->nac", A, A)
    occ = jax.random.randint(ks[2], (n,), 0, 50)
    return w, Minv, occ


def _region_catalog(key, N, d, regions=4, noise=0.02):
    e, _ = env.make_catalog_env(key, n_users=16, d=d, n_clusters=regions,
                                n_items=N, n_candidates=10,
                                item_noise_scale=noise)
    return serve.make_catalog(env.catalog_embeddings(e)), e


# ---------------------------------------------------------------------------
# bound soundness + exact pruning
# ---------------------------------------------------------------------------


def test_tile_bounds_dominate_member_scores():
    """tb[u, t] >= score(u, i) for every live item i in tile t — with
    non-trivial Minv (anisotropic confidence) and mixed occupancies, so
    every term of the bound (estimate + radius + the min() of the two
    confidence majorants) is exercised."""
    key = jax.random.PRNGKey(0)
    n, d, N, tile = 12, 16, 1024, 128
    w, Minv, occ = _stats(key, n, d, scale=0.4)
    cat, _ = _region_catalog(jax.random.PRNGKey(1), N, d, noise=0.2)
    cl = itemclub.build_clusters(cat, tile_items=tile)
    tb = tile_bounds(w, Minv, occ, 0.3, cl.tile_mu, cl.tile_r, cl.tile_xn,
                     cl.tile_n)

    x = cl.emb_sorted
    est = w @ x.T
    quad = jnp.einsum("ua,uab,ib->ui", w * 0 + 1, Minv * 0 + jnp.eye(d), x)
    q = jnp.sqrt(jnp.maximum(
        jnp.einsum("ia,uab,ib->ui", x, Minv, x), 0.0))
    s = est + 0.3 * q * jnp.sqrt(jnp.log1p(occ.astype(jnp.float32)))[:, None]
    s = jnp.where(cl.live_sorted[None] > 0, s, -jnp.inf)
    per_tile_max = jnp.max(s.reshape(n, N // tile, tile), axis=2)
    assert np.all(np.asarray(tb) + 1e-6 >= np.asarray(per_tile_max))
    # and the slack is not doing the work: the margin is the real bound
    assert np.all(np.asarray(tb) - BOUND_SLACK + 1e-3
                  >= np.asarray(per_tile_max))


@pytest.mark.parametrize("catalog_kind", ["random", "ties", "regions"])
@pytest.mark.parametrize("engine", ["reference", "pallas"])
def test_pruned_equals_unpruned_bit_exact(catalog_kind, engine):
    """The acceptance criterion: pruned shortlist ids AND scores
    bit-equal to the unpruned stream — including under adversarial
    near-ties (the catalog is 64 embeddings repeated, so (score, id)
    tie-breaks decide every slot)."""
    key = jax.random.PRNGKey(7)
    n, d, N, tile, K = 24, 16, 2048, 256, 16
    if catalog_kind == "random":
        cat = serve.random_catalog(jax.random.PRNGKey(1), N, d)
    elif catalog_kind == "ties":
        base = jax.random.normal(jax.random.PRNGKey(2), (64, d))
        base /= jnp.linalg.norm(base, axis=-1, keepdims=True)
        cat = serve.make_catalog(jnp.tile(base, (N // 64, 1)))
    else:
        cat, _ = _region_catalog(jax.random.PRNGKey(3), N, d)
    # retired items in the mix: dead slots sort to the trailing tiles
    cat, _ = serve.retire_items(
        cat, jax.random.permutation(jax.random.PRNGKey(4), N)[:100])
    cat = serve.publish(cat)

    w, Minv, occ = _stats(key, n, d)
    cl = itemclub.build_clusters(cat, tile_items=tile, n_anchors=128)
    bank = cat.serving
    s0, i0 = topk_ref(w, Minv, occ, bank.emb, bank.live, 0.3, K)
    tb = tile_bounds(w, Minv, occ, 0.3, cl.tile_mu, cl.tile_r, cl.tile_xn,
                     cl.tile_n)
    if engine == "reference":
        s1, i1, sk, tot = topk_ref_pruned(
            w, Minv, occ, cl.emb_sorted, cl.live_sorted, cl.perm, 0.3, K,
            tb, row_block=4)
    else:
        s1, i1, sk, tot = topk_ops.topk_pruned(
            w, Minv, occ, cl.emb_sorted, cl.live_sorted, cl.perm, 0.3, K,
            tb, use_pallas=True, block_users=8, interpret=True)
    np.testing.assert_array_equal(np.asarray(s0), np.asarray(s1))
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    assert 0 <= int(sk) <= int(tot)


def test_pruned_region_catalog_actually_skips():
    """On a well-separated region catalog with informative users the
    reference pruned path must skip a substantial share of tiles — the
    perf claim at test scale, not just exactness."""
    d, N, tile, K = 16, 4096, 256, 16
    cat, e = _region_catalog(jax.random.PRNGKey(5), N, d, regions=8,
                             noise=0.01)
    n = e.theta.shape[0]
    w = e.theta
    Minv = jnp.broadcast_to(jnp.eye(d), (n, d, d)).astype(jnp.float32)
    occ = jnp.full((n,), 50, jnp.int32)
    cl = itemclub.build_clusters(cat, tile_items=tile)
    tb = tile_bounds(w, Minv, occ, 0.3, cl.tile_mu, cl.tile_r, cl.tile_xn,
                     cl.tile_n)
    s1, i1, sk, tot = topk_ref_pruned(
        w, Minv, occ, cl.emb_sorted, cl.live_sorted, cl.perm, 0.3, K, tb,
        row_block=4)
    s0, i0 = topk_ref(w, Minv, occ, cat.serving.emb, cat.serving.live,
                      0.3, K)
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    assert int(sk) / int(tot) > 0.3, (int(sk), int(tot))


def test_pallas_pruned_skip_count_matches_natural_order_rule():
    """The Pallas pruned stream visits tiles in natural order and skips a
    tile for a user block iff every user's bound is below that user's
    running shortlist floor — the k-th best live score among the tiles
    before it.  Its skip counter must equal that rule evaluated on the
    host, and the shortlist must still equal the unpruned one."""
    d, N, tile, K, bu = 16, 4096, 256, 16, 8
    cat, e = _region_catalog(jax.random.PRNGKey(5), N, d, regions=8,
                             noise=0.01)
    occ = jnp.full((e.theta.shape[0],), 50, jnp.int32)
    cl = itemclub.build_clusters(cat, tile_items=tile)
    Minv = jnp.broadcast_to(jnp.eye(d), (e.theta.shape[0], d, d))
    tb0 = tile_bounds(e.theta, Minv, occ, 0.3, cl.tile_mu, cl.tile_r,
                      cl.tile_xn, cl.tile_n)
    # a segment-aware batch: users sharing a best tile share a block
    order = jnp.argsort(jnp.argmax(tb0, axis=1), stable=True)
    w, tb = e.theta[order], tb0[order]
    Minv = Minv[order]
    s1, i1, sk, tot = topk_ops.topk_pruned(
        w, Minv, occ, cl.emb_sorted, cl.live_sorted, cl.perm, 0.3, K, tb,
        use_pallas=True, block_users=bu, interpret=True)
    _, i0 = topk_ref(w, Minv, occ, cat.serving.emb, cat.serving.live, 0.3, K)
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))

    x = np.asarray(cl.emb_sorted, np.float64)
    s = (np.asarray(w, np.float64) @ x.T
         + 0.3 * np.linalg.norm(x, axis=1)[None] * np.sqrt(np.log1p(50.0)))
    s = np.where(np.asarray(cl.live_sorted)[None] > 0, s, -np.inf)
    T = N // tile
    floors = np.full((w.shape[0], T), -np.inf)
    for t in range(1, T):
        seen = np.sort(s[:, :t * tile], axis=1)
        floors[:, t] = seen[:, -K] if t * tile >= K else -np.inf
    below = np.asarray(tb) < floors
    want = below.reshape(-1, bu, T).all(axis=1).sum()
    assert int(tot) == T * (w.shape[0] // bu)
    assert 0 < want == int(sk), (want, int(sk))


# ---------------------------------------------------------------------------
# clustering structure
# ---------------------------------------------------------------------------


def test_build_clusters_recovers_planted_regions():
    """Items of the same planted region land in the same cluster, items
    of different regions in different clusters (low noise, so the CLUB
    threshold separates them cleanly), and the tile layout is coherent:
    every tile holds items of one region."""
    d, N = 16, 2048
    cat, e = _region_catalog(jax.random.PRNGKey(11), N, d, regions=4,
                             noise=0.01)
    cl = itemclub.build_clusters(cat, tile_items=128, n_anchors=128)
    assert int(cl.n_clusters) == 4
    labels = np.asarray(cl.labels)
    regions = np.asarray(e.item_region)
    # labels and regions agree up to relabeling: one label per region
    for r in range(4):
        assert len(set(labels[regions == r])) == 1
    assert len({labels[regions == r][0] for r in range(4)}) == 4


def test_build_clusters_reference_pallas_identical():
    """The anchor CLUB graph through the reference vs interpret-mode
    Pallas graph engines yields the identical clustering — labels, perm,
    tile tables, everything (the stage-2 parity guarantee carried to the
    item side)."""
    cat, _ = _region_catalog(jax.random.PRNGKey(13), 1024, 16, noise=0.05)
    stats = itemclub.init_stats(1024)
    # non-trivial learned rewards so the rhat feature participates
    stats = itemclub.observe_served(
        stats, jnp.arange(512, dtype=jnp.int32),
        jax.random.uniform(jax.random.PRNGKey(1), (512,)))
    a = itemclub.build_clusters(cat, stats, tile_items=128,
                                kind="reference")
    b = itemclub.build_clusters(cat, stats, tile_items=128, kind="pallas",
                                interpret=True)
    for fa, fb in zip(a, b):
        np.testing.assert_array_equal(np.asarray(fa), np.asarray(fb))


def test_reward_statistics_split_geometric_twins():
    """Two geometrically identical item groups with divergent LEARNED
    rewards separate into different clusters — the item-side CLUB
    insight: clustering is on (embedding, rhat), not embedding alone."""
    d, N = 8, 256
    base = jnp.ones((1, d)) / jnp.sqrt(d)
    emb = jnp.tile(base, (N, 1))
    cat = serve.make_catalog(emb)
    stats = itemclub.init_stats(N)
    ids = jnp.arange(N, dtype=jnp.int32)
    for _ in range(50):   # occ high enough that cb_width tightens
        stats = itemclub.observe_served(
            stats, ids, jnp.where(ids < N // 2, 1.0, 0.0))
    # n_anchors = N: every item is an anchor (the exact CLUB graph) —
    # the bounded-anchor default would take the FIRST live slots, which
    # here are all high-reward twins, leaving the low-reward group
    # without a representative
    cl = itemclub.build_clusters(cat, stats, tile_items=32, n_anchors=N,
                                 beta=1.0)
    labels = np.asarray(cl.labels)
    assert len(set(labels[: N // 2])) == 1
    assert len(set(labels[N // 2:])) == 1
    assert labels[0] != labels[-1]
    # and without the learned statistics they collapse to one cluster
    cl0 = itemclub.build_clusters(cat, tile_items=32, n_anchors=N)
    assert len(set(np.asarray(cl0.labels))) == 1


# ---------------------------------------------------------------------------
# feedback statistics
# ---------------------------------------------------------------------------


def test_observe_served_duplicates_padding_and_reset():
    st = itemclub.init_stats(8)
    st = itemclub.observe_served(st, jnp.array([3, 3, -1, 9, 7]),
                                 jnp.array([1.0, 0.5, 9.0, 9.0, 2.0]))
    assert int(st.occ[3]) == 2 and abs(float(st.rsum[3]) - 1.5) < 1e-6
    assert int(st.occ[7]) == 1 and float(st.rsum[7]) == 2.0
    assert int(jnp.sum(st.occ)) == 3          # padding + OOB dropped
    # valid mask quarantines (e.g. stale-feedback) entries
    st2 = itemclub.observe_served(st, jnp.array([7, 7]),
                                  jnp.array([1.0, 1.0]),
                                  valid=jnp.array([True, False]))
    assert int(st2.occ[7]) == 2

    # a reclaimed slot resets after the publish that re-seats it
    cat = serve.make_catalog(jnp.eye(8, 4), capacity=8)
    cat, _ = serve.retire_items(cat, jnp.array([3]))
    cat = serve.publish(cat)
    cat, slots, _ = serve.add_items(cat, jnp.ones((1, 4)))
    cat = serve.publish(cat)
    assert int(slots[0]) == 3                 # lowest dead slot reclaimed
    st3 = itemclub.reset_new_slots(st, cat)
    assert int(st3.occ[3]) == 0 and float(st3.rsum[3]) == 0.0
    assert int(st3.occ[7]) == int(st.occ[7])


# ---------------------------------------------------------------------------
# churn safety
# ---------------------------------------------------------------------------


def _mk_session(n_users, d):
    return serve.OnlineBandit.create(n_users, d, HYPER, policy="distclub")


def _reward_fn_for(theta):
    def reward_fn(key, uids, ctx, choice):
        return env.step_rewards(key, theta[uids], ctx, choice)
    return reward_fn


def test_stale_cluster_table_falls_back_to_unpruned():
    """Mass-retire + publish WITHOUT rebuilding: the pruned transaction
    must serve the identical items as the unpruned one off the NEW
    catalog (``pruned_active == 0``), never prune with stale bounds;
    a refresh re-arms pruning."""
    n_users, d, N = 32, 8, 512
    cat, e = _region_catalog(jax.random.PRNGKey(21), N, d)
    reward_fn = _reward_fn_for(e.theta[:n_users])
    cl = serve.build_clusters(cat, tile_items=64)
    sa, sb = _mk_session(n_users, d), _mk_session(n_users, d)
    uids = jnp.arange(32, dtype=jnp.int32)

    k = jax.random.PRNGKey(0)
    sa, ia, _ = serve.step_catalog(sa, k, uids, cat, reward_fn, k_short=16)
    sb, ib, _, rm = serve.step_catalog(sb, k, uids, cat, reward_fn,
                                       k_short=16, clusters=cl)
    np.testing.assert_array_equal(np.asarray(ia), np.asarray(ib))
    assert int(rm.pruned_active) == 1

    # mass retire half the catalog + fresh arrivals, publish — the swap
    # the cluster table has never seen
    cat, _ = serve.retire_items(cat, jnp.arange(0, N, 2, dtype=jnp.int32))
    fresh, _ = env.sample_churn_items(e, jax.random.PRNGKey(5), 64)
    cat, _, _ = serve.add_items(cat, fresh)
    cat = serve.publish(cat)
    assert int(cl.epoch) != int(cat.epoch)

    k = jax.random.PRNGKey(1)
    sa, ia, _ = serve.step_catalog(sa, k, uids, cat, reward_fn, k_short=16)
    sb, ib, _, rm = serve.step_catalog(sb, k, uids, cat, reward_fn,
                                       k_short=16, clusters=cl)
    np.testing.assert_array_equal(np.asarray(ia), np.asarray(ib))
    assert int(rm.pruned_active) == 0 and int(rm.tiles_total) == 0

    cl = serve.refresh_clusters(cl, cat)
    k = jax.random.PRNGKey(2)
    sa, ia, _ = serve.step_catalog(sa, k, uids, cat, reward_fn, k_short=16)
    sb, ib, _, rm = serve.step_catalog(sb, k, uids, cat, reward_fn,
                                       k_short=16, clusters=cl)
    np.testing.assert_array_equal(np.asarray(ia), np.asarray(ib))
    assert int(rm.pruned_active) == 1


def test_refresh_under_sustained_churn_stays_exact():
    """tests/test_churn.py-style sustained churn: every epoch retires a
    random slice, lands fresh arrivals, publishes, rebuilds — the layout
    must stay a true permutation with exact live accounting, and the
    pruned serving path must stay bit-equal to unpruned throughout."""
    n_users, d, N = 16, 8, 512
    cat, e = _region_catalog(jax.random.PRNGKey(31), N, d)
    reward_fn = _reward_fn_for(e.theta[:n_users])
    stats = serve.init_stats(N)
    cl = serve.build_clusters(cat, stats, tile_items=64)
    sa, sb = _mk_session(n_users, d), _mk_session(n_users, d)

    for t in range(6):
        k = jax.random.PRNGKey(100 + t)
        uids = jax.random.randint(jax.random.PRNGKey(200 + t), (16,), 0,
                                  n_users)
        sa, ia, ma = serve.step_catalog(sa, k, uids, cat, reward_fn,
                                        k_short=16)
        sb, ib, mb, rm = serve.step_catalog(sb, k, uids, cat, reward_fn,
                                            k_short=16, clusters=cl)
        np.testing.assert_array_equal(np.asarray(ia), np.asarray(ib))
        assert float(ma.reward) == float(mb.reward)
        stats = serve.observe_served(
            stats, ia, jnp.ones((ia.shape[0],), jnp.float32))

        live_ids = np.flatnonzero(np.asarray(cat.serving.live) > 0)
        kill = jax.random.choice(jax.random.PRNGKey(300 + t),
                                 jnp.asarray(live_ids), (40,),
                                 replace=False)
        cat, _ = serve.retire_items(cat, kill)
        fresh, _ = env.sample_churn_items(e, jax.random.PRNGKey(400 + t),
                                          30)
        cat, _, _ = serve.add_items(cat, fresh)
        cat = serve.publish(cat)
        stats = serve.reset_new_slots(stats, cat)
        cl = serve.refresh_clusters(cl, cat, stats)
        assert int(cl.epoch) == int(cat.epoch)
        perm = np.sort(np.asarray(cl.perm))
        np.testing.assert_array_equal(perm, np.arange(N))
        assert float(jnp.sum(cl.live_sorted)) == float(
            jnp.sum(cat.serving.live))
        assert int(jnp.sum(cl.tile_n)) == int(jnp.sum(cat.serving.live))


# ---------------------------------------------------------------------------
# sharded parity
# ---------------------------------------------------------------------------


def test_pruned_8dev_item_sharded_matches_single_host():
    """Pruned serving on an 8-device item-sharded mesh == single-host
    pruned == single-host unpruned, bit for bit: the replicated cluster
    tables slice into per-shard position ranges whose shortlists merge
    by (score, id) value to the identical global shortlist."""
    out = _run_with_devices("""
        import numpy as np
        import jax, jax.numpy as jnp
        from repro import serve
        from repro.core import catalog as catalog_mod, env
        from repro.core.types import BanditHyper
        from repro.distributed.distclub_shard import named_shardings

        N_USERS, D, N_ITEMS, KS = 64, 8, 1024, 16
        hyper = BanditHyper(alpha=0.3, sigma=4, max_rounds=1, gamma=1.5,
                            n_candidates=10)
        e, _ = env.make_catalog_env(jax.random.PRNGKey(0), N_USERS, D, 4,
                                    N_ITEMS, n_candidates=10,
                                    item_noise_scale=0.02)
        cat = serve.make_catalog(env.catalog_embeddings(e))
        cat, _ = serve.retire_items(cat, jnp.array([3, 17, 800], jnp.int32))
        cat = serve.publish(cat)
        # tile_items=16: 1024 / (16 * 8 shards) = 8 whole tiles per shard
        clusters = serve.build_clusters(cat, tile_items=16)
        theta = e.theta

        def reward_fn(key, uids, ctx, choice):
            return env.step_rewards(key, theta[uids], ctx, choice)

        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("users",))
        s1 = serve.OnlineBandit.create(N_USERS, D, hyper, policy="distclub")
        s8 = serve.OnlineBandit.sharded(mesh, N_USERS, D, hyper,
                                        policy="distclub")
        su = serve.OnlineBandit.create(N_USERS, D, hyper, policy="distclub")
        cat8 = jax.device_put(
            cat, named_shardings(mesh, catalog_mod.specs(("users",))))
        for i in range(4):
            k = jax.random.PRNGKey(i)
            uids = jax.random.permutation(
                jax.random.PRNGKey(100 + i), N_USERS).astype(jnp.int32)
            s1, i1, m1, r1 = serve.step_catalog(
                s1, k, uids, cat, reward_fn, k_short=KS, clusters=clusters)
            s8, i8, m8, r8 = serve.step_catalog(
                s8, k, uids, cat8, reward_fn, k_short=KS, clusters=clusters)
            su, iu, mu = serve.step_catalog(su, k, uids, cat, reward_fn,
                                            k_short=KS)
            np.testing.assert_array_equal(np.asarray(i1), np.asarray(i8))
            np.testing.assert_array_equal(np.asarray(i1), np.asarray(iu))
            assert float(m1.reward) == float(m8.reward) == float(mu.reward)
            assert int(r1.pruned_active) == int(r8.pruned_active) == 1
            assert int(r8.tiles_total) == int(r1.tiles_total)
        np.testing.assert_array_equal(np.asarray(s1.state.occ),
                                      np.asarray(s8.state.occ))
        np.testing.assert_allclose(np.asarray(s1.state.Minv),
                                   np.asarray(s8.state.Minv), atol=1e-6)
        print("PRUNED-SHARD-PARITY-OK", int(r1.tiles_skipped))
    """)
    assert "PRUNED-SHARD-PARITY-OK" in out


# ---------------------------------------------------------------------------
# guardrail telemetry
# ---------------------------------------------------------------------------


def test_guarded_pruned_telemetry_and_recall(tmp_path):
    n_users, d, N = 32, 8, 512
    cat, e = _region_catalog(jax.random.PRNGKey(41), N, d, noise=0.01)
    reward_fn = _reward_fn_for(e.theta[:n_users])
    cl = serve.build_clusters(cat, tile_items=64)
    sess = _mk_session(n_users, d)
    g = serve.Guarded.create(
        sess, CheckpointManager(tmp_path / "ck"),
        serve.GuardrailConfig(recall_floor=0.99, warmup=0), catalog=cat)
    uids = jnp.arange(32, dtype=jnp.int32)
    for t in range(3):
        g, items, m, rm = g.step_catalog(
            jax.random.PRNGKey(t), uids, reward_fn=reward_fn, k_short=16,
            probe_recall=True, clusters=cl)
    assert g.gs.ema_tiles_skipped is not None
    assert g.gs.ema_tiles_skipped == pytest.approx(rm.skip_ratio(),
                                                   abs=0.5)
    # pruning is exact, so the unpruned-oracle recall probe saturates
    assert g.gs.ema_recall == pytest.approx(1.0)
    assert not g.tripped
