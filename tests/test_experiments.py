"""The online experimentation layer: sticky routing over policy arms.

Router invariants covered:
  * sticky assignment is deterministic and STABLE: shrinking one arm's
    fraction migrates exactly the users whose hash left the shrinking
    arm, and nobody else; unchanged fraction vectors migrate nobody;
  * a single-arm experiment at fraction 1.0 is BIT-identical to a plain
    `OnlineBandit` session — choices, decision ids, and state —
    single-host and on an 8-device mesh (subprocess);
  * a checkpoint round-trip through `CheckpointManager` resumes
    bit-identical routing and choices;
  * a sign-flip-poisoned arm breaches its per-arm guardrail, is
    auto-disabled (state rolled back, traffic re-routed to survivors —
    who keep every user they already had), and the experiment keeps
    serving; the LAST enabled arm is never disabled;
  * the Thompson-sampling meta-selector concentrates traffic on a
    planted-best arm, floors every enabled arm, and re-weights only at
    epoch boundaries.
"""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import serve
from repro.core import env
from repro.core.types import BanditHyper
from repro.serve import experiments, guardrails
from repro.train.checkpoint import CheckpointManager

from test_distributed import _run_with_devices

N, D, K, B = 32, 8, 10, 16
HYPER = BanditHyper(sigma=4, max_rounds=1, gamma=1.5, n_candidates=K)


def _session(policy="linucb", alpha=0.03, capacity=128, ttl=16):
    return serve.OnlineBandit.create(
        N, D, HYPER._replace(alpha=alpha), policy=policy, refresh_every=N,
        pending_capacity=capacity, pending_ttl=ttl)


@pytest.fixture(scope="module")
def world():
    e, _ = env.make_synthetic_env(jax.random.PRNGKey(0), N, D, 4, K)
    return e


def _uids(i, n=B):
    # includes negative padding rows
    return jax.random.randint(jax.random.PRNGKey(1000 + i), (n,), -2, N)


def _ctx(i, n=B):
    c = jax.random.normal(jax.random.PRNGKey(2000 + i), (n, K, D))
    return c / jnp.sqrt(jnp.float32(D))


def _assert_states_equal(a, b):
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# sticky assignment
# ---------------------------------------------------------------------------


def test_sticky_assignment_deterministic_and_padded():
    uids = jnp.arange(-4, N)
    a1 = experiments.assign_arms(uids, (0.5, 0.5), (True, True), salt=9)
    a2 = experiments.assign_arms(uids, (0.5, 0.5), (True, True), salt=9)
    np.testing.assert_array_equal(np.asarray(a1), np.asarray(a2))
    assert (np.asarray(a1)[:4] == -1).all()          # uid<0 is padding
    assert set(np.asarray(a1)[4:]) <= {0, 1}
    # a different salt is a different (but still deterministic) split
    a3 = experiments.assign_arms(uids, (0.5, 0.5), (True, True), salt=10)
    assert (np.asarray(a1)[4:] != np.asarray(a3)[4:]).any()


def test_fraction_shrink_migrates_only_leavers():
    """0.5 -> 0.3 on arm 0: the only moves are OUT of the shrinking arm
    (hash in the surrendered [0.3, 0.5) band); growing/unchanged arms
    keep every user."""
    uids = jnp.arange(4 * N)
    before = np.asarray(experiments.assign_arms(
        uids, (0.5, 0.5), (True, True), salt=5))
    after = np.asarray(experiments.assign_arms(
        uids, (0.3, 0.7), (True, True), salt=5))
    moved = before != after
    assert moved.any()                       # the band is non-empty
    assert (before[moved] == 0).all() and (after[moved] == 1).all()


def test_unchanged_fractions_migrate_nobody():
    uids = jnp.arange(4 * N)
    f = (0.2, 0.5, 0.3)
    before = np.asarray(experiments.assign_arms(
        uids, f, (True,) * 3, salt=5))
    again = np.asarray(experiments.assign_arms(
        uids, f, (True,) * 3, salt=5))
    np.testing.assert_array_equal(before, again)


def test_disable_reroutes_without_migrating_survivors():
    uids = jnp.arange(4 * N)
    f = (0.4, 0.3, 0.3)
    before = np.asarray(experiments.assign_arms(
        uids, f, (True,) * 3, salt=2))
    after = np.asarray(experiments.assign_arms(
        uids, f, (True, False, True), salt=2))
    assert not (after == 1).any()            # nobody routes to the dead arm
    survivors = before != 1
    # every user of a surviving arm stays put
    np.testing.assert_array_equal(before[survivors], after[survivors])


# ---------------------------------------------------------------------------
# single-arm bit-parity with a plain session
# ---------------------------------------------------------------------------


def test_single_arm_parity_with_plain_session(world):
    """One arm at fraction 1.0 == a plain buffered session: choices,
    decision ids, and state bit-identical through issue/feedback rounds
    (the router masks to uid -1, which is the padding no-op)."""
    exp = experiments.create([_session()])
    plain = _session()
    for i in range(5):
        u, ctx = _uids(i), _ctx(i)
        exp, c_e, ids_e = experiments.recommend(exp, u, ctx)
        plain, c_p, ids_p = serve.recommend(plain, u, ctx)
        np.testing.assert_array_equal(np.asarray(c_e), np.asarray(c_p))
        np.testing.assert_array_equal(np.asarray(ids_e), np.asarray(ids_p))
        r, _, _, _ = env.step_rewards(jax.random.PRNGKey(3000 + i),
                                      world.theta[u], ctx, c_p)
        k = jax.random.PRNGKey(4000 + i)
        exp = experiments.observe_delayed(exp, ids_e, r, key=k)
        plain = serve.observe_delayed(plain, ids_p, r, key=k)
    _assert_states_equal(exp.arms[0].state, plain.state)
    _assert_states_equal(exp.arms[0].pending, plain.pending)


def test_single_arm_parity_sync_step(world):
    """The synchronous routed `step` has the same single-arm parity."""
    theta = world.theta

    def reward_fn(key, uids, ctx, choice):
        safe = jnp.clip(uids, 0, N - 1)
        return env.step_rewards(key, theta[safe], ctx, choice)

    exp = experiments.create([_session(capacity=0)])
    plain = _session(capacity=0)
    for i in range(4):
        u, ctx = _uids(i), _ctx(i)
        k = jax.random.PRNGKey(i)
        exp, c_e, _ = experiments.step(exp, k, u, ctx, reward_fn)
        plain, c_p, _ = serve.step(plain, k, u, ctx, reward_fn)
        np.testing.assert_array_equal(np.asarray(c_e), np.asarray(c_p))
    _assert_states_equal(exp.arms[0].state, plain.state)


def test_single_arm_parity_8dev_sharded():
    """Single-arm parity holds when the arm session is sharded over an
    8-device mesh — the router's masking composes with shard_map."""
    out = _run_with_devices("""
        import numpy as np
        import jax, jax.numpy as jnp
        from repro import serve
        from repro.serve import experiments
        from repro.core import env
        from repro.core.types import BanditHyper

        N, D, K, B = 64, 8, 10, 16
        hyper = BanditHyper(sigma=4, max_rounds=1, gamma=1.5,
                            n_candidates=K)
        e, _ = env.make_synthetic_env(jax.random.PRNGKey(0), N, D, 4, K)
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("users",))
        mk = lambda: serve.OnlineBandit.sharded(
            mesh, N, D, hyper, policy="distclub", refresh_every=2 * N,
            pending_capacity=128, pending_ttl=16)
        exp = experiments.create([mk()])
        plain = mk()
        for i in range(4):
            u = jax.random.randint(jax.random.PRNGKey(100 + i), (B,),
                                   -2, N)
            ctx = jax.random.normal(jax.random.PRNGKey(200 + i),
                                    (B, K, D)) / np.sqrt(D)
            exp, c_e, ids_e = experiments.recommend(exp, u, ctx)
            plain, c_p, ids_p = serve.recommend(plain, u, ctx)
            np.testing.assert_array_equal(np.asarray(c_e), np.asarray(c_p))
            np.testing.assert_array_equal(np.asarray(ids_e),
                                          np.asarray(ids_p))
            r, _, _, _ = env.step_rewards(jax.random.PRNGKey(300 + i),
                                          e.theta[u], ctx, c_p)
            k = jax.random.PRNGKey(400 + i)
            exp = experiments.observe_delayed(exp, ids_e, r, key=k)
            plain = serve.observe_delayed(plain, ids_p, r, key=k)
        for x, y in zip(jax.tree_util.tree_leaves(exp.arms[0].state),
                        jax.tree_util.tree_leaves(plain.state)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        print("EXP-SHARD-PARITY-OK")
    """)
    assert "EXP-SHARD-PARITY-OK" in out


def test_fraction_one_masked_path_parity(world):
    """fractions (1.0, 0.0): arm 0 owns all traffic THROUGH the masked
    multi-arm router (no single-arm fast path) and must still be
    bit-identical to the plain session — the mask-to-uid-(-1) no-op
    contract, exercised for real."""
    exp = experiments.create([_session(), _session(alpha=1.0)],
                             fractions=(1.0, 0.0), salt=6)
    plain = _session()
    for i in range(4):
        u, ctx = _uids(i), _ctx(i)
        exp, c_e, ids_e = experiments.recommend(exp, u, ctx)
        plain, c_p, ids_p = serve.recommend(plain, u, ctx)
        np.testing.assert_array_equal(np.asarray(c_e), np.asarray(c_p))
        # arm-encoded ids: local * 2 + 0
        np.testing.assert_array_equal(
            np.asarray(ids_e),
            np.where(np.asarray(ids_p) >= 0, np.asarray(ids_p) * 2, -1))
        r, _, _, _ = env.step_rewards(jax.random.PRNGKey(3000 + i),
                                      world.theta[u], ctx, c_p)
        k = jax.random.PRNGKey(4000 + i)
        exp = experiments.observe_delayed(exp, ids_e, r, key=k)
        plain = serve.observe_delayed(plain, ids_p, r, key=k)
    _assert_states_equal(exp.arms[0].state, plain.state)
    # the zero-fraction arm never saw a request
    _assert_states_equal(exp.arms[1].state,
                         _session(alpha=1.0).state)


# ---------------------------------------------------------------------------
# multi-arm routing
# ---------------------------------------------------------------------------


def test_multi_arm_routing_matches_masked_sub_sessions(world):
    """Each arm's state evolves exactly as a standalone session fed the
    masked sub-batches — routing is partition + merge, nothing more."""
    exp = experiments.create([_session(alpha=0.03), _session(alpha=1.0)],
                             salt=7)
    solo = [_session(alpha=0.03), _session(alpha=1.0)]
    for i in range(4):
        u, ctx = _uids(i), _ctx(i)
        arm_of = np.asarray(experiments.assign_arms(exp, u))
        exp, c_e, ids_e = experiments.recommend(exp, u, ctx)
        r, _, _, _ = env.step_rewards(jax.random.PRNGKey(3000 + i),
                                      world.theta[u], ctx, c_e)
        k = jax.random.PRNGKey(4000 + i)
        for a in range(2):
            u_a = jnp.where(jnp.asarray(arm_of) == a, u, -1)
            solo[a], c_s, ids_s = serve.recommend(solo[a], u_a, ctx)
            sel = arm_of == a
            np.testing.assert_array_equal(np.asarray(c_e)[sel],
                                          np.asarray(c_s)[sel])
            # decision ids are arm-encoded: local * n_arms + arm
            np.testing.assert_array_equal(
                np.asarray(ids_e)[sel],
                np.asarray(ids_s)[sel] * 2 + a)
            solo[a] = serve.observe_delayed(solo[a], ids_s, r, key=k)
        exp = experiments.observe_delayed(exp, ids_e, r, key=k)
    for a in range(2):
        _assert_states_equal(exp.arms[a].state, solo[a].state)


# ---------------------------------------------------------------------------
# checkpoint round-trip
# ---------------------------------------------------------------------------


def test_checkpoint_roundtrip_resumes_identical_routing(world, tmp_path):
    """save -> (new process) restore resumes bit-identical routing AND
    choices — salt, fractions, selector posteriors, arm states, pending
    rings all round-trip."""
    def mk():
        return experiments.create(
            [_session(alpha=0.03), _session(alpha=1.0)], salt=13,
            selector=experiments.make_selector(2, epoch_rounds=3))

    ck = CheckpointManager(tmp_path / "exp", keep=2)
    exp = mk()
    exp, _ = experiments.run_experiment(exp, world.theta, 7, batch=B,
                                        key=5)
    experiments.save(exp, ck, 7)
    cont, _ = experiments.run_experiment(exp, world.theta, 6, batch=B,
                                         key=99)

    fresh, step = experiments.restore(mk(), ck, 7)
    assert step == 7 and fresh.steps == exp.steps
    assert fresh.fractions == exp.fractions
    # routing is bit-identical after restore ...
    uids = jnp.arange(N)
    np.testing.assert_array_equal(
        np.asarray(experiments.assign_arms(exp, uids)),
        np.asarray(experiments.assign_arms(fresh, uids)))
    # ... and so is everything the resumed run produces
    cont2, _ = experiments.run_experiment(fresh, world.theta, 6, batch=B,
                                          key=99)
    for a in range(2):
        _assert_states_equal(cont.arms[a].state, cont2.arms[a].state)
    np.testing.assert_array_equal(cont.totals["reward"],
                                  cont2.totals["reward"])
    assert cont.fractions == cont2.fractions


def test_restore_empty_dir_is_noop(tmp_path):
    exp = experiments.create([_session()])
    same, step = experiments.restore(
        exp, CheckpointManager(tmp_path / "none"), None)
    assert step is None and same.steps == 0


# ---------------------------------------------------------------------------
# per-arm guardrails: auto-disable + re-route
# ---------------------------------------------------------------------------


def _poisoned_loop(exp, theta, rounds, flip_arm):
    """Drive the experiment with arm ``flip_arm``'s delivered rewards
    sign-flipped (the targeted poisoning fault) and everyone else
    healthy."""
    A = exp.n_arms
    for i in range(rounds):
        u = jax.random.randint(jax.random.PRNGKey(100 + i), (B,), 0, N)
        ctx = _ctx(i)
        exp, ch, ids = experiments.recommend(exp, u, ctx)
        r, _, _, _ = env.step_rewards(jax.random.PRNGKey(300 + i),
                                      theta[u], ctx, ch)
        arm_of = jnp.where(ids >= 0, ids % A, -1)
        r = jnp.where(arm_of == flip_arm, -r, r)
        exp = experiments.observe_delayed(exp, ids, r,
                                          key=jax.random.PRNGKey(400 + i))
    return exp


def test_poisoned_arm_auto_disabled_and_rerouted(world):
    cfg = guardrails.GuardrailConfig(ctr_floor=0.2, warmup=2 * B,
                                     ema=0.6, cooldown=2)
    exp = experiments.create(
        [_session(alpha=0.03), _session(alpha=0.03)], salt=3,
        guard_cfg=cfg, snapshot_every=2)
    healthy_anchor = exp.arms[1].state
    exp = _poisoned_loop(exp, world.theta, 12, flip_arm=1)
    assert exp.enabled == (True, False)
    kinds = [e[0] for e in exp.events]
    assert "disable" in kinds
    # all traffic now routes to the survivor; the survivor's users never
    # migrated (sticky fallback)
    uids = jnp.arange(N)
    arm = np.asarray(experiments.assign_arms(exp, uids))
    assert (arm == 0).all()
    # the poisoned arm's state was rolled back to a pre-breach snapshot
    # (its pending ring cleared), not left poisoned
    assert exp.arms[1].pending.uid.max() < 0
    disable_step = [e[1] for e in exp.events if e[0] == "disable"][0]
    assert disable_step <= 12
    # the rollback anchor is from before the breach tripped: folding the
    # flipped rewards for `disable_step` more rounds from the anchor
    # diverges, so the restored state must be older than the final
    # poisoned state would have been
    assert exp.guards[1].rollbacks == 1
    del healthy_anchor


def test_last_enabled_arm_is_never_disabled(world):
    cfg = guardrails.GuardrailConfig(ctr_floor=0.2, warmup=B, ema=0.6,
                                     cooldown=1)
    exp = experiments.create([_session(alpha=0.03)], guard_cfg=cfg)
    exp = _poisoned_loop(exp, world.theta, 8, flip_arm=0)
    assert exp.enabled == (True,)
    assert any(e[0] == "breach-last-arm" for e in exp.events)


# ---------------------------------------------------------------------------
# the probation window
# ---------------------------------------------------------------------------


def test_probation_survivor_intervals_untouched():
    """The ISSUE regression test: through disable -> throttled probation
    -> restore, a surviving arm's sticky hash interval never moves — not
    one user a healthy arm owns is reassigned at any stage."""
    uids = jnp.arange(4 * N)
    f = (0.4, 0.3, 0.3)
    full = np.asarray(experiments.assign_arms(uids, f, (True,) * 3,
                                              salt=2))
    dis = np.asarray(experiments.assign_arms(uids, f, (True, False, True),
                                             salt=2))
    prob = np.asarray(experiments.assign_arms(
        uids, f, (True,) * 3, salt=2, scale=(1.0, 0.25, 1.0)))
    survivors = full != 1
    np.testing.assert_array_equal(full[survivors], dis[survivors])
    np.testing.assert_array_equal(full[survivors], prob[survivors])
    # the probation arm takes back a non-empty strict SUBSET of its own
    # full interval...
    back = prob == 1
    assert back.any() and back.sum() < (full == 1).sum()
    assert (full[back] == 1).all()
    # ...and every user it does NOT take back stays exactly where the
    # disable-time fallback sent them
    fell = (full == 1) & ~back
    np.testing.assert_array_equal(prob[fell], dis[fell])
    # restore == the original full cut, bit for bit
    rest = np.asarray(experiments.assign_arms(
        uids, f, (True,) * 3, salt=2, scale=(1.0, 1.0, 1.0)))
    np.testing.assert_array_equal(rest, full)


def test_probation_lifecycle_reenable_throttled_then_restore(world):
    """A breached arm sits out `probation_tx` transactions, comes back
    throttled to `probation_fraction` of its own interval, and a clean
    probation window restores it to full traffic."""
    cfg = guardrails.GuardrailConfig(ctr_floor=0.2, warmup=2 * B, ema=0.6,
                                     cooldown=2)
    exp = experiments.create(
        [_session(alpha=0.03), _session(alpha=0.03)], salt=3,
        guard_cfg=cfg, snapshot_every=2, probation_tx=3,
        probation_fraction=0.25)
    rounds = 0
    while exp.enabled == (True, True) and rounds < 20:
        exp = _poisoned_loop(exp, world.theta, 1, flip_arm=1)
        rounds += 1
    assert exp.enabled == (True, False)
    assert exp.stages[1] == experiments.BENCHED
    uids = jnp.arange(N)
    full = np.asarray(experiments.assign_arms(
        uids, exp.fractions, (True, True), salt=3))
    # three clean routing transactions serve out the bench window
    exp = _poisoned_loop(exp, world.theta, 3, flip_arm=-1)
    assert exp.enabled == (True, True)
    assert exp.stages[1] == experiments.PROBATION
    assert any(e[0] == "probation" for e in exp.events)
    arm = np.asarray(experiments.assign_arms(exp, uids))
    back = arm == 1
    assert back.any() and back.sum() < (full == 1).sum()
    assert (full[back] == 1).all()
    np.testing.assert_array_equal(arm[full == 0], full[full == 0])
    # a clean probation window promotes the arm back to its full interval
    exp = _poisoned_loop(exp, world.theta, 3, flip_arm=-1)
    assert exp.stages[1] == experiments.HEALTHY
    assert any(e[0] == "restore" for e in exp.events)
    np.testing.assert_array_equal(
        np.asarray(experiments.assign_arms(exp, uids)), full)


def test_probation_second_breach_is_permanent(world):
    """An arm that breaches again WHILE ON probation is permanently
    disabled — no further probation windows."""
    cfg = guardrails.GuardrailConfig(ctr_floor=0.2, warmup=B, ema=0.6,
                                     cooldown=1)
    exp = experiments.create(
        [_session(alpha=0.03), _session(alpha=0.03)], salt=3,
        guard_cfg=cfg, snapshot_every=2, probation_tx=2,
        probation_fraction=0.5)
    exp = _poisoned_loop(exp, world.theta, 40, flip_arm=1)
    assert any(e[0] == "probation" for e in exp.events)
    assert any(e[0] == "disable-permanent" for e in exp.events)
    assert exp.stages[1] == experiments.PERMANENT
    assert exp.enabled == (True, False)
    # permanently out: more clean traffic never re-enables it
    exp = _poisoned_loop(exp, world.theta, 6, flip_arm=-1)
    assert exp.enabled == (True, False)
    assert exp.stages[1] == experiments.PERMANENT


# ---------------------------------------------------------------------------
# the meta-selector
# ---------------------------------------------------------------------------


def test_selector_concentrates_on_planted_best(world):
    """Two copycat arms with absurd exploration vs one tuned arm: the
    posterior routes the majority of traffic to the tuned arm, keeps the
    floor on the others, and only moves fractions at epoch boundaries."""
    arms = [_session(alpha=0.05), _session(alpha=50.0),
            _session(alpha=50.0)]
    exp = experiments.create(
        arms, names=("good", "noisy1", "noisy2"), salt=11,
        selector=experiments.make_selector(3, epoch_rounds=10, floor=0.05))
    exp, rep = experiments.run_experiment(exp, world.theta, 60, batch=B,
                                          key=5)
    assert rep.leader == "good"
    assert rep.fractions[0] >= 0.6
    assert all(f > 0 for f in rep.fractions)         # floored, not starved
    # fractions moved only at epoch boundaries (10 rounds apart)
    assert [s % 10 for s, _ in rep.shares] == [0] * len(rep.shares)
    assert len(rep.shares) == 7                      # t=0 + 6 epochs


def test_meta_selector_reward_vs_best_fixed_arm():
    """The price of learning which arm wins: over 60 rounds of 16
    requests on 64 users, the selector's realized reward over three arms
    (one tuned, two with absurd exploration) stays at or above 90% of its
    seeded ratio (0.957) to the tuned arm serving all traffic alone on
    the identical stream."""
    n = 64
    e, _ = env.make_synthetic_env(jax.random.PRNGKey(0), n, D, 4, K)

    def arm(alpha):
        return serve.OnlineBandit.create(
            n, D, HYPER._replace(alpha=alpha), policy="linucb",
            refresh_every=n, pending_capacity=256, pending_ttl=16)

    exp = experiments.create(
        [arm(0.05), arm(50.0), arm(50.0)],
        names=("best", "noisy1", "noisy2"), salt=11,
        selector=experiments.make_selector(3, epoch_rounds=10, floor=0.05))
    _, rep = experiments.run_experiment(exp, e.theta, 60, batch=B, key=5)
    solo = experiments.create([arm(0.05)], names=("best",))
    _, fixed = experiments.run_experiment(solo, e.theta, 60, batch=B, key=5)
    ratio = sum(rep.reward) / sum(fixed.reward)
    assert ratio >= 0.9 * 0.957, ratio


def test_selector_bucketed_posteriors_update(world):
    sel = experiments.make_selector(2, epoch_rounds=5,
                                    bucket_edges=(3, 21))
    exp = experiments.create([_session(), _session(alpha=1.0)],
                             selector=sel, salt=1)
    exp, _ = experiments.run_experiment(exp, world.theta, 10, batch=B,
                                        key=2)
    sel = exp.selector
    # prior mass was 1+1 per cell; observed feedback landed somewhere
    assert float(sel.alpha.sum() + sel.beta.sum()) > 2.0 * sel.alpha.size
    assert sel.alpha.shape == (3, 2)


def test_report_fields(world):
    exp = experiments.create([_session(), _session(alpha=1.0)],
                             names=("a", "b"), salt=4)
    exp, rep = experiments.run_experiment(exp, world.theta, 6, batch=B,
                                          key=8)
    assert rep.rounds == 6 and rep.names == ("a", "b")
    assert len(rep.reward) == 2 and len(rep.matched_ratio) == 2
    assert sum(rep.interactions) > 0
    assert rep.regret == tuple(b - e for b, e in zip(rep.best,
                                                     rep.expected))
    assert np.isfinite(rep.z_leading_pair)
    assert rep.leader in rep.names and rep.runner_up in rep.names
