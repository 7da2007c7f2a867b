"""Epoch-consistent live catalog churn: double-buffered swaps,
stale-feedback quarantine, and churn fault injection.

Acceptance criteria covered here:
  * REGRESSION: feedback for an item retired between issue and delivery
    is QUARANTINED when the fold sees the current catalog — before the
    epoch/quarantine machinery it folded into learner state (the
    corrupt fold this file pins);
  * staleness bound: an in-flight shortlist tolerates exactly ONE stale
    epoch — issue-epoch feedback folds across a single publish, is
    quarantined from two publishes on, regardless of item liveness;
  * zero-churn serving is BIT-identical whether or not churn is staged:
    staging never perturbs the serving bank, single-host and on an
    8-device item-sharded mesh (subprocess);
  * the conservation identity
        issued == matched + in_flight + expired + dropped + stale
    holds EXACTLY after every delivery under sustained churn combined
    with delay / loss / duplication / torn swaps — single-host and
    8-device (subprocess), with identical seeded counters;
  * `Guarded` snapshots capture (state, catalog, epoch) as ONE unit: a
    churn-ceiling breach rolls all three back together.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import serve
from repro.core import catalog as catalog_mod, env
from repro.core.types import BanditHyper
from repro.serve import faults, guardrails, pending as pending_mod
from repro.train.checkpoint import CheckpointManager

from test_distributed import _run_with_devices

N, D, K, B = 32, 8, 10, 16
HYPER = BanditHyper(sigma=4, max_rounds=1, gamma=1.5, n_candidates=K)


def _session(capacity=128, ttl=16):
    return serve.OnlineBandit.create(
        N, D, HYPER, policy="distclub", refresh_every=N,
        pending_capacity=capacity, pending_ttl=ttl)


def _world(n_items=64, seed=3):
    e, _ = env.make_catalog_env(jax.random.PRNGKey(seed), N, D, 4,
                                n_items, n_candidates=K)
    return e, serve.make_catalog(env.catalog_embeddings(e))


def _reward_fn(theta):
    def reward_fn(key, uids, ctx, choice):
        return env.step_rewards(key, theta[uids], ctx, choice)
    return reward_fn


def _uids(i, n=B):
    return jax.random.randint(jax.random.PRNGKey(1000 + i), (n,), 0, N)


def _assert_states_equal(a, b):
    for la, lb in zip(jax.tree_util.tree_leaves(a),
                      jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


# ---------------------------------------------------------------------------
# the regression this PR exists for: retired-item feedback must not fold
# ---------------------------------------------------------------------------


def test_retired_item_feedback_quarantined_not_folded():
    """Issue against epoch e, retire+publish every served item, deliver:
    with the catalog in the fold, every entry is quarantined (``stale``)
    and the learner state does not move.  Without it (the pre-epoch
    fold), the same delivery FOLDS — the corrupt behavior this test
    pins as the legacy path and the catalog-aware path must not share.
    """
    _, cat = _world()
    sess, items, ids, slots, ctx = serve.recommend_catalog(
        _session(), _uids(0), cat, k_short=8)
    served = jnp.unique(items)
    churned, _ = catalog_mod.retire_items(cat, served)
    churned = catalog_mod.publish(churned)

    before = sess.state
    quarantined = serve.observe_delayed(
        sess, ids, jnp.ones((B,), jnp.float32),
        key=jax.random.PRNGKey(0), catalog=churned)
    _assert_states_equal(before, quarantined.state)
    st = serve.pending_stats(quarantined)
    assert st["stale"] == B and st["matched"] == 0, st
    assert st["issued"] == (st["matched"] + st["in_flight"]
                           + st["expired"] + st["dropped"] + st["stale"])

    # the legacy catalog-blind fold DOES move state on the same input —
    # the corruption the quarantine exists to stop
    legacy = serve.observe_delayed(sess, ids, jnp.ones((B,), jnp.float32),
                                   key=jax.random.PRNGKey(0))
    assert serve.pending_stats(legacy)["matched"] == B
    occ_moved = np.asarray(legacy.state.occ) != np.asarray(before.occ)
    assert occ_moved.any()


def test_slot_reuse_after_retire_does_not_alias():
    """Retire a served item, publish, re-add a DIFFERENT item onto the
    freed slot, publish again: delivered feedback for the old decision
    must be quarantined even though the slot is live again — ``born``
    distinguishes the generations."""
    _, cat = _world()
    sess, items, ids, _, _ = serve.recommend_catalog(
        _session(), _uids(0), cat, k_short=8)
    victim = jnp.asarray([int(np.asarray(items)[0])], jnp.int32)
    c2, _ = catalog_mod.retire_items(cat, victim)
    c2 = catalog_mod.publish(c2)
    c2, slots2, _ = catalog_mod.add_items(
        c2, jnp.ones((1, D), jnp.float32) / np.sqrt(D))
    c2 = catalog_mod.publish(c2)
    assert np.asarray(slots2).tolist() == np.asarray(victim).tolist()
    assert int(c2.serving.live[int(victim[0])]) == 1

    sess = serve.observe_delayed(sess, ids, jnp.ones((B,), jnp.float32),
                                 key=jax.random.PRNGKey(0), catalog=c2)
    st = serve.pending_stats(sess)
    # every decision on the victim slot is stale (born > issue epoch);
    # note epoch lag is already 2 here, so the whole batch quarantines —
    # the aliasing hazard needs the batch to be un-foldable anyway
    assert st["stale"] == B and st["matched"] == 0, st


# ---------------------------------------------------------------------------
# the staleness bound: exactly one epoch of tolerated lag
# ---------------------------------------------------------------------------


def test_staleness_bound_exactly_one_epoch():
    """No-op publishes leave every item live, so liveness never blocks
    the fold: epoch lag alone draws the line.  lag 0 and lag 1 fold,
    lag 2 quarantines."""
    _, cat = _world()
    for lag, want_stale in [(0, 0), (1, 0), (2, B)]:
        sess, _, ids, _, _ = serve.recommend_catalog(
            _session(), _uids(0), cat, k_short=8)
        c = cat
        for _ in range(lag):
            c = catalog_mod.publish(c)      # nothing staged: item no-op
        sess = serve.observe_delayed(
            sess, ids, jnp.ones((B,), jnp.float32),
            key=jax.random.PRNGKey(1), catalog=c)
        st = serve.pending_stats(sess)
        assert st["stale"] == want_stale, (lag, st)
        assert st["matched"] == B - want_stale, (lag, st)


# ---------------------------------------------------------------------------
# zero-churn bit-parity: staging never touches serving
# ---------------------------------------------------------------------------


def test_staged_unpublished_churn_serves_bit_identical():
    """A session serving against a catalog with STAGED (unpublished)
    adds+retires makes bit-identical decisions and folds to
    bit-identical state vs the untouched catalog, with zero quarantine
    and epoch pinned at 0."""
    e, cat = _world()
    reward_fn = _reward_fn(e.theta)
    staged, _ = catalog_mod.retire_items(cat,
                                         jnp.array([1, 7, 30], jnp.int32))
    staged, _, _ = catalog_mod.add_items(
        staged, jnp.full((4, D), 0.5, jnp.float32))
    assert int(catalog_mod.staged_churn(staged)) > 0

    a, b = _session(), _session()
    for i in range(4):
        key = jax.random.PRNGKey(i)
        a, it_a, ids_a, slots_a, ctx_a = serve.recommend_catalog(
            a, _uids(i), cat, k_short=8)
        b, it_b, ids_b, slots_b, _ = serve.recommend_catalog(
            b, _uids(i), staged, k_short=8)
        np.testing.assert_array_equal(np.asarray(it_a), np.asarray(it_b))
        realized, _, _, _ = reward_fn(key, _uids(i), ctx_a, slots_a)
        a = serve.observe_delayed(a, ids_a, realized, key=key, catalog=cat)
        b = serve.observe_delayed(b, ids_b, realized, key=key,
                                  catalog=staged)
    _assert_states_equal(a.state, b.state)
    for s in (a, b):
        st = serve.pending_stats(s)
        assert st["stale"] == 0 and st["matched"] == 4 * B, st
    assert int(staged.epoch) == 0


def test_zero_churn_harness_identical_with_and_without_catalog_fold():
    """Churn-free traffic through the harness: passing the (never
    published) catalog to the fold changes nothing — same counters, same
    reward — i.e. the quarantine machinery is invisible until an epoch
    actually flips."""
    e, cat = _world(n_items=96)
    _, plain = faults.run_faulted_catalog(
        _session(capacity=256), e, 12, faults.FaultSpec(seed=2, p_delay=0.3,
                                                        p_loss=0.1),
        catalog=cat, k_short=8, batch=B, key=7, assert_conservation=True)
    assert plain.pending["stale"] == 0 and plain.publishes == 0
    assert plain.pending["issued"] == 12 * B


# ---------------------------------------------------------------------------
# conservation under churn x delivery faults (property-style grid)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("spec", [
    faults.FaultSpec(seed=1, p_delay=0.4, max_delay=4, p_loss=0.1,
                     p_dup=0.1, churn_every=2, churn_add=6,
                     churn_retire=6),
    faults.FaultSpec(seed=2, p_delay=0.3, max_delay=5, p_loss=0.05,
                     p_dup=0.05, churn_every=3, churn_add=8,
                     churn_retire=8, p_torn=0.5, swap_stall_rounds=1),
    faults.FaultSpec(seed=3, p_delay=0.5, max_delay=6, p_loss=0.2,
                     churn_every=2, churn_add=4, churn_retire=12,
                     flash_crowd_at=6, flash_crowd_size=16,
                     mass_retire_at=10),
], ids=["sustained", "torn_stalled", "flash_then_mass_retire"])
def test_conservation_identity_exact_under_churn_and_faults(spec):
    """issued == matched + in_flight + expired + dropped + stale after
    EVERY delivery transaction (asserted inside the harness), for churn
    crossed with delay/loss/dup/torn/stall — and some feedback really
    was quarantined, so the identity is exercised, not vacuous."""
    e, cat = _world(n_items=96, seed=spec.seed)
    sess, rep = faults.run_faulted_catalog(
        _session(capacity=256), e, 20, spec, catalog=cat, k_short=8,
        batch=B, key=spec.seed, assert_conservation=True)
    st = rep.pending
    assert st["issued"] == 20 * B
    assert st["issued"] == (st["matched"] + st["in_flight"]
                           + st["expired"] + st["dropped"] + st["stale"])
    assert st["stale"] > 0, st
    assert rep.publishes > 0
    assert int(pending_mod.conservation_gap(sess.pending)) == 0


# ---------------------------------------------------------------------------
# seeded levels of the churn scenarios against a churn-free control
# ---------------------------------------------------------------------------

LV_USERS, LV_BATCH, LV_ITEMS, LV_ITEM_CAPACITY, LV_KS = 128, 32, 384, 512, 16
LV_ROUNDS = 60
# identical delay/loss faults on every scenario (the control included), so
# the ratios isolate the churn itself
_LV_DELIVERY = dict(seed=5, p_delay=0.25, max_delay=3, p_loss=0.05)


@pytest.fixture(scope="module")
def churn_levels_world():
    e, _ = env.make_catalog_env(jax.random.PRNGKey(0), LV_USERS, D, 4,
                                LV_ITEMS, n_candidates=K)
    cat = serve.make_catalog(env.catalog_embeddings(e),
                             capacity=LV_ITEM_CAPACITY)
    nochurn = _levels_run(e, cat, faults.FaultSpec(**_LV_DELIVERY))
    return e, cat, nochurn


def _levels_run(e, cat, spec):
    sess = serve.OnlineBandit.create(
        LV_USERS, D, HYPER, policy="distclub", refresh_every=LV_USERS,
        pending_capacity=512, pending_ttl=16)
    _, rep = faults.run_faulted_catalog(
        sess, e, LV_ROUNDS, spec, catalog=cat, k_short=LV_KS,
        batch=LV_BATCH, key=11, assert_conservation=True)
    return rep


# floors: 90% of the seeded values these scenarios recorded at these sizes
@pytest.mark.parametrize("spec,recorded", [
    (None, dict(matched_ratio=0.9453125)),
    (faults.FaultSpec(**_LV_DELIVERY, churn_every=3, churn_add=8,
                      churn_retire=8),
     dict(matched_ratio=0.9328125, stale_ratio=0.022916666666666665,
          reward_vs_nochurn_ratio=1.0009496676163343)),
    (faults.FaultSpec(**_LV_DELIVERY, churn_every=5, churn_add=8,
                      churn_retire=8, flash_crowd_at=10,
                      flash_crowd_size=24),
     dict(matched_ratio=0.9458333333333333,
          stale_ratio=0.0067708333333333336,
          reward_vs_nochurn_ratio=0.9895536562203229)),
], ids=["nochurn", "sustained", "flash_crowd"])
def test_churn_levels_hold_against_nochurn(churn_levels_world, spec,
                                           recorded):
    """Over 60 rounds of 32 requests on 128 users and 384 items, the
    share of decisions folded, the share quarantined as stale, and the
    realized reward against the churn-free control on identical traffic
    stay at or above 90% of their seeded values: churn costs little
    learning, and the quarantine really fires."""
    e, cat, nochurn = churn_levels_world
    rep = nochurn if spec is None else _levels_run(e, cat, spec)
    st = rep.pending
    got = {"matched_ratio": st["matched"] / st["issued"],
           "stale_ratio": st["stale"] / st["issued"],
           "reward_vs_nochurn_ratio": rep.reward / nochurn.reward}
    for name, value in recorded.items():
        assert got[name] >= 0.9 * value, (name, got[name], value)
    if spec is None:
        assert st["stale"] == 0, st


def test_conservation_and_parity_8dev_item_sharded():
    """The same seeded churn+faults run on an 8-device item-sharded mesh:
    the conservation identity holds after every delivery AND every final
    counter matches the single-host run exactly (the per-shard stale
    mask combines to the same global verdicts)."""
    out = _run_with_devices("""
        import numpy as np
        import jax, jax.numpy as jnp
        from repro import serve
        from repro.core import catalog as catalog_mod, env
        from repro.core.types import BanditHyper
        from repro.distributed.distclub_shard import named_shardings
        from repro.serve import faults

        N, D, B = 64, 8, 16
        hyper = BanditHyper(sigma=4, max_rounds=1, gamma=1.5,
                            n_candidates=10)
        e, _ = env.make_catalog_env(jax.random.PRNGKey(0), N, D, 4, 128,
                                    n_candidates=10)
        cat = serve.make_catalog(env.catalog_embeddings(e))
        spec = faults.FaultSpec(seed=4, p_delay=0.4, max_delay=4,
                                p_loss=0.1, p_dup=0.05, churn_every=3,
                                churn_add=8, churn_retire=8, p_torn=0.5)

        def mk(sharded):
            if not sharded:
                return serve.OnlineBandit.create(
                    N, D, hyper, policy="distclub", refresh_every=2 * N,
                    pending_capacity=256, pending_ttl=16), cat
            from repro.launch.mesh import make_mesh
            mesh = make_mesh((8,), ("users",))
            s = serve.OnlineBandit.sharded(
                mesh, N, D, hyper, policy="distclub",
                refresh_every=2 * N, pending_capacity=256,
                pending_ttl=16)
            c = jax.device_put(
                cat, named_shardings(mesh, catalog_mod.specs(("users",))))
            return s, c

        reports = []
        for sharded in (False, True):
            s, c = mk(sharded)
            _, rep = faults.run_faulted_catalog(
                s, e, 15, spec, catalog=c, k_short=16, batch=B, key=9,
                assert_conservation=True)
            reports.append(rep)
        r1, r8 = reports
        assert r1.pending == r8.pending, (r1.pending, r8.pending)
        assert r1.pending["stale"] > 0
        assert r1.pending["issued"] == (
            r1.pending["matched"] + r1.pending["in_flight"]
            + r1.pending["expired"] + r1.pending["dropped"]
            + r1.pending["stale"])
        assert (r1.publishes, r1.items_added, r1.items_retired) == \\
               (r8.publishes, r8.items_added, r8.items_retired)
        assert float(r1.reward) == float(r8.reward)
        print("CHURN-SHARD-CONSERVATION-OK")
    """)
    assert "CHURN-SHARD-CONSERVATION-OK" in out


# ---------------------------------------------------------------------------
# guardrails: (state, catalog, epoch) roll back as one unit
# ---------------------------------------------------------------------------


def test_guarded_snapshot_includes_catalog_and_epoch(tmp_path):
    """A churn-ceiling breach rolls back state AND catalog to the
    snapshot's epoch: the restored pair serves exactly what the
    snapshot-time pair served (the satellite fix: snapshots that
    captured only the state resumed against a future catalog)."""
    e, cat = _world(n_items=96)
    reward_fn = _reward_fn(e.theta)
    cfg = guardrails.GuardrailConfig(
        ctr_floor=0.0, churn_ceiling=0.25, warmup=10_000,
        snapshot_every=2, cooldown=2)
    g = guardrails.Guarded.create(
        _session(capacity=256), CheckpointManager(tmp_path / "gc", keep=4),
        cfg, catalog=cat)

    # healthy churn under traffic: small swaps stay below the ceiling
    for i in range(4):
        g, _, ids, slots, ctx = g.recommend_catalog(_uids(i), k_short=8)
        realized, _, _, _ = reward_fn(jax.random.PRNGKey(i), _uids(i),
                                      ctx, slots)
        g = g.observe_delayed(ids, realized, key=jax.random.PRNGKey(i))
        g, _ = g.stage_churn(add=jnp.full((2, D), 0.3, jnp.float32))
        g = g.publish()
    assert g.gs.rollbacks == 0
    epoch_before = int(g.catalog.epoch)
    snap_state, snap_cat = g.session.state, g.catalog
    assert epoch_before == 4

    # mass retirement blows through the ceiling -> epoch-consistent
    # rollback of the (state, catalog) pair
    live = np.nonzero(np.asarray(g.catalog.serving.live) > 0)[0]
    g, _ = g.stage_churn(retire=jnp.asarray(live[:60], dtype=jnp.int32))
    g = g.publish()
    assert g.gs.rollbacks == 1, g.events
    ev = [x for x in g.events if x[0] == "rollback"]
    assert ev[0][2] == ("churn_ceiling",)
    # catalog rolled back WITH the state: epoch and liveness match the
    # last healthy snapshot, not the poisoned publish
    assert int(g.catalog.epoch) == epoch_before
    _assert_states_equal(g.catalog, snap_cat)
    _assert_states_equal(g.session.state, snap_state)
    # ring cleared, id counter monotone: stale pre-rollback feedback
    # can never alias a post-rollback decision
    st = serve.pending_stats(g.session)
    assert st["in_flight"] == 0 and st["issued"] > 0


def test_checkpoint_roundtrip_state_and_catalog_pair(tmp_path):
    """The Guarded snapshot payload ({state, catalog}) restores through
    CheckpointManager.restore_latest as a pair, epochs included."""
    _, cat = _world()
    cat2, _ = catalog_mod.retire_items(cat, jnp.array([4, 9], jnp.int32))
    cat2 = catalog_mod.publish(cat2)
    sess = _session()
    ck = CheckpointManager(tmp_path / "pair", keep=2)
    ck.save({"state": sess.state, "catalog": cat2}, 7)
    like = {"state": _session().state,
            "catalog": serve.make_catalog(jnp.zeros((64, D), jnp.float32))}
    restored, step = ck.restore_latest(like)
    assert step == 7
    assert int(restored["catalog"].epoch) == 1
    _assert_states_equal(restored["catalog"], cat2)
    _assert_states_equal(restored["state"], sess.state)
