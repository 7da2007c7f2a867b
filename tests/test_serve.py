"""The online serving API: `OnlineBandit` sessions on the stage engine.

Covers the redesign's acceptance criteria:
  * duplicate-user batches are exact (the old `observe` lost feedback
    via last-writer-wins scatter);
  * one `step` over a distinct-user batch matches the offline stage
    engine (`runtime.stages` via `distclub.stage3`) — bit-exact choices,
    state to 1e-5 (observed exact) — single-host and 8-device sharded;
  * the transaction runs jit-end-to-end with the refresh scheduled by
    `lax.cond` (no host sync), and through the pallas-interpret engine;
  * a kill/restore round-trip through `CheckpointManager` resumes with
    bit-identical subsequent choices;
  * all four policies serve through the one `Policy` protocol.
"""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import serve
from repro.core import distclub, env, env_ops, linucb
from repro.core.backend import BackendConfig
from repro.core.types import BanditHyper
from repro.runtime import stages
from repro.train.checkpoint import CheckpointManager

from test_distributed import _run_with_devices

N, D, K = 32, 8, 10
HYPER = BanditHyper(sigma=4, max_rounds=1, gamma=1.5, n_candidates=K)


@pytest.fixture(scope="module")
def planted():
    e, _ = env.make_synthetic_env(jax.random.PRNGKey(0), N, D, 4, K)
    return env_ops.synthetic_ops(e)


@functools.lru_cache(maxsize=None)
def _reward_fn(ops):
    # cached per EnvOps: the session's compiled transactions are keyed on
    # reward_fn identity, so a fresh closure per call would retrace the
    # whole step each iteration
    def reward_fn(key, uids, contexts, choice):
        # env draws are keyed per global user id; occ is unused by the
        # synthetic generator beyond its shape
        return ops.rewards_fn(key, jnp.zeros_like(uids), contexts, choice, 0)
    return reward_fn


def _ctx(ops, i):
    k_ctx, k_rew = jax.random.split(jax.random.PRNGKey(i))
    return ops.contexts_fn(k_ctx, jnp.zeros((N,), jnp.int32), 0), k_rew


# ---------------------------------------------------------------------------
# duplicate-user feedback
# ---------------------------------------------------------------------------


def test_duplicate_user_batch_is_exact(planted):
    """A batch with the same user twice advances occ by 2 and folds both
    rewards — matching the sequential Sherman-Morrison fold exactly."""
    sess = serve.OnlineBandit.create(N, D, HYPER, policy="linucb")
    uids = jnp.array([3, 3, 5], jnp.int32)
    ctx = jax.random.normal(jax.random.PRNGKey(9), (3, K, D))
    ctx = ctx / jnp.linalg.norm(ctx, axis=-1, keepdims=True)
    rewards = jnp.array([1.0, 0.5, 0.25])

    def fixed_rewards(key, u, c, ch):
        return rewards

    sess2, ch, m = serve.step(sess, jax.random.PRNGKey(0), uids, ctx,
                              fixed_rewards)
    assert int(sess2.state.occ[3]) == 2
    assert int(sess2.state.occ[5]) == 1
    assert int(m.interactions) == 3

    x = jnp.take_along_axis(ctx, ch[:, None, None], axis=1)[:, 0]
    Minv = linucb.sherman_morrison(jnp.eye(D), x[0])
    Minv = linucb.sherman_morrison(Minv, x[1])
    np.testing.assert_allclose(np.asarray(sess2.state.Minv[3]),
                               np.asarray(Minv), atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(sess2.state.b[3]),
        np.asarray(rewards[0] * x[0] + rewards[1] * x[1]), atol=1e-6)
    # the old API's last-writer-wins failure mode: occ would be 1 and
    # only x[1]'s update present
    assert not np.allclose(np.asarray(sess2.state.Minv[3]),
                           np.asarray(linucb.sherman_morrison(jnp.eye(D),
                                                              x[1])))


def test_padded_requests_are_ignored(planted):
    """user_id < 0 marks a padding slot: no state change, not counted."""
    ops = planted
    sess = serve.OnlineBandit.create(N, D, HYPER, policy="distclub")
    uids = jnp.array([2, -1, 7], jnp.int32)
    ctx, k_rew = _ctx(ops, 0)
    sess2, _, m = serve.step(sess, k_rew, uids, ctx[:3], _reward_fn(ops))
    assert int(m.interactions) == 2
    assert int(sess2.state.occ.sum()) == 2
    assert int(sess2.state.since_refresh) == 2


# ---------------------------------------------------------------------------
# serving-vs-offline parity (the stage engine is the oracle)
# ---------------------------------------------------------------------------


def test_step_matches_stage3_round(planted):
    """One full-batch serving step == one stage-3 round of the offline
    engine from the same stage-2 state: bit-exact choices, exact state."""
    ops = planted
    st0 = distclub.init_state(N, D, HYPER)
    st2 = distclub.stage2(st0, HYPER, D)
    stage_key = jax.random.PRNGKey(7)
    st3, m3 = distclub.stage3(st2, ops, stage_key, HYPER)

    sess = serve.refresh(
        serve.OnlineBandit.create(N, D, HYPER, policy="distclub"))
    # forced refresh == stage 2 on the init state
    np.testing.assert_array_equal(np.asarray(sess.state.labels),
                                  np.asarray(st2.graph.labels))
    np.testing.assert_array_equal(np.asarray(sess.state.adj),
                                  np.asarray(st2.graph.adj))

    # replicate the round's key schedule (scan step key -> ctx/reward)
    k0 = jax.random.split(stage_key, 1)[0]
    k_ctx, k_rew = jax.random.split(k0)
    ctx = ops.contexts_fn(k_ctx, st2.lin.occ, 0)
    sess2, choices, m = serve.step(
        sess, k_rew, jnp.arange(N, dtype=jnp.int32), ctx, _reward_fn(ops))

    # bit-exact choices vs the stage pipeline's own fused choose
    be = BackendConfig.create().interact(N, D, K)
    uMcinv, ubc, umean = distclub.serving_snapshot(st2)
    use_own = stages.beta_gate(HYPER, st2.lin.occ, umean)
    w, minv_eff = stages.mix_scores(
        use_own, linucb.user_vector(st2.lin.Minv, st2.lin.b),
        linucb.user_vector(uMcinv, ubc), st2.lin.Minv, uMcinv)
    _, c_ref = be.choose(w, minv_eff, ctx, st2.lin.occ, HYPER.alpha)
    np.testing.assert_array_equal(np.asarray(choices), np.asarray(c_ref))

    # state parity with the full stage-3 round (observed exact; the
    # acceptance tolerance is 1e-5)
    np.testing.assert_array_equal(np.asarray(sess2.state.occ),
                                  np.asarray(st3.lin.occ))
    np.testing.assert_allclose(np.asarray(sess2.state.Minv),
                               np.asarray(st3.lin.Minv), atol=1e-5)
    np.testing.assert_allclose(np.asarray(sess2.state.b),
                               np.asarray(st3.lin.b), atol=1e-5)
    assert float(m.reward) == float(np.asarray(m3.reward).sum())


def test_step_sharded_8dev_matches_single_host():
    """The sharded serving binding runs the identical transaction: choices
    bit-exact per step, state equal after refreshes fired inside jit."""
    out = _run_with_devices("""
        import numpy as np
        import jax, jax.numpy as jnp
        from repro import serve
        from repro.core import env, env_ops
        from repro.core.types import BanditHyper

        N, D, K = 64, 8, 10
        hyper = BanditHyper(sigma=4, max_rounds=1, gamma=1.5,
                            n_candidates=K)
        e, _ = env.make_synthetic_env(jax.random.PRNGKey(0), N, D, 4, K)
        ops = env_ops.synthetic_ops(e)

        def reward_fn(key, uids, ctx, choice):
            return ops.rewards_fn(key, jnp.zeros_like(uids), ctx, choice, 0)

        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("users",))
        s1 = serve.OnlineBandit.create(N, D, hyper, policy="distclub",
                                       refresh_every=2 * N)
        s8 = serve.OnlineBandit.sharded(mesh, N, D, hyper,
                                        policy="distclub",
                                        refresh_every=2 * N)
        for i in range(5):
            k_ctx, k_rew = jax.random.split(jax.random.PRNGKey(i))
            ctx = ops.contexts_fn(k_ctx, jnp.zeros((N,), jnp.int32), 0)
            uids = jax.random.permutation(
                jax.random.PRNGKey(100 + i), N).astype(jnp.int32)
            s1, c1, m1 = serve.step(s1, k_rew, uids, ctx, reward_fn)
            s8, c8, m8 = serve.step(s8, k_rew, uids, ctx, reward_fn)
            np.testing.assert_array_equal(np.asarray(c1), np.asarray(c8))
            assert float(m1.reward) == float(m8.reward)
        # two refreshes fired inside the jitted transaction by now
        assert int(s8.state.since_refresh) == N
        np.testing.assert_array_equal(np.asarray(s1.state.occ),
                                      np.asarray(s8.state.occ))
        np.testing.assert_array_equal(np.asarray(s1.state.labels),
                                      np.asarray(s8.state.labels))
        np.testing.assert_array_equal(np.asarray(s1.state.adj),
                                      np.asarray(s8.state.adj))
        np.testing.assert_allclose(np.asarray(s1.state.Minv),
                                   np.asarray(s8.state.Minv), atol=1e-6)
        print("SERVE-SHARD-PARITY-OK")
    """)
    assert "SERVE-SHARD-PARITY-OK" in out


def test_serving_through_pallas_interpret_engine(planted):
    """The fused engine path (pallas, interpret off-TPU) serves with
    identical choices and 1e-5-close state to the reference engine."""
    ops = planted
    mk = lambda kind, interp: serve.OnlineBandit.create(
        N, D, HYPER, policy="distclub", refresh_every=N,
        backend=kind, interpret=interp)
    sp, sr = mk("pallas", True), mk("reference", None)
    for i in range(2):
        ctx, k_rew = _ctx(ops, i)
        uids = jnp.arange(N, dtype=jnp.int32)
        sp, cp, _ = serve.step(sp, k_rew, uids, ctx, _reward_fn(ops))
        sr, cr, _ = serve.step(sr, k_rew, uids, ctx, _reward_fn(ops))
        np.testing.assert_array_equal(np.asarray(cp), np.asarray(cr))
    np.testing.assert_allclose(np.asarray(sp.state.Minv),
                               np.asarray(sr.state.Minv), atol=1e-5)


# ---------------------------------------------------------------------------
# refresh scheduling + checkpointing
# ---------------------------------------------------------------------------


def test_refresh_fires_inside_jit(planted):
    """The interaction-budget cond re-clusters without any host sync."""
    ops = planted
    sess = serve.OnlineBandit.create(N, D, HYPER, policy="distclub",
                                     refresh_every=2 * N)
    assert int(sess.state.comm_bytes) == 0
    for i in range(4):
        ctx, k_rew = _ctx(ops, i)
        sess, _, _ = serve.step(sess, k_rew, jnp.arange(N, dtype=jnp.int32),
                                ctx, _reward_fn(ops))
    # 4N interactions / budget 2N -> exactly two stage-2 refreshes
    assert float(sess.state.comm_bytes) == 2 * stages.stage2_comm_bytes(N, D)
    assert int(sess.state.since_refresh) == 0


def test_checkpoint_restore_resumes_bit_identical(planted, tmp_path):
    """Kill/restore through CheckpointManager: the restarted replica's
    subsequent choices are bit-identical to the uninterrupted run."""
    ops = planted
    ck = CheckpointManager(tmp_path / "svc", keep=2)
    sess = serve.OnlineBandit.create(N, D, HYPER, policy="distclub",
                                     refresh_every=N)
    uids = jnp.arange(N, dtype=jnp.int32)
    for i in range(3):
        ctx, k_rew = _ctx(ops, i)
        sess, _, _ = serve.step(sess, k_rew, uids, ctx, _reward_fn(ops))
    sess.save(ck, 3)

    cont_choices, cont = [], sess
    for i in range(3, 6):
        ctx, k_rew = _ctx(ops, i)
        cont, ch, _ = serve.step(cont, k_rew, uids, ctx, _reward_fn(ops))
        cont_choices.append(np.asarray(ch))

    # the "crashed replica": a fresh session restored from the checkpoint
    restored, step = serve.OnlineBandit.create(
        N, D, HYPER, policy="distclub", refresh_every=N).restore(ck)
    assert step == 3
    for i, want in zip(range(3, 6), cont_choices):
        ctx, k_rew = _ctx(ops, i)
        restored, ch, _ = serve.step(restored, k_rew, uids, ctx,
                                     _reward_fn(ops))
        np.testing.assert_array_equal(np.asarray(ch), want)
    np.testing.assert_array_equal(np.asarray(restored.state.occ),
                                  np.asarray(cont.state.occ))
    np.testing.assert_array_equal(np.asarray(restored.state.Minv),
                                  np.asarray(cont.state.Minv))


def test_restore_on_empty_directory(planted, tmp_path):
    ck = CheckpointManager(tmp_path / "empty")
    sess = serve.OnlineBandit.create(N, D, HYPER, policy="linucb")
    same, step = sess.restore(ck)
    assert step is None and same is sess


# ---------------------------------------------------------------------------
# the Policy protocol
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("policy", serve.POLICIES)
def test_every_policy_serves_and_beats_random(planted, policy):
    """All four bandits through the one protocol; the learners beat the
    RAN baseline on the planted environment.  DCCB gets a short buffer
    (its statistics lag by `buffer_size` interactions) and a long gossip
    period — at this tiny scale each gossip round cuts a wrong-cluster
    edge and RESETS both endpoints (the paper's protocol), so frequent
    gossip erases more than it shares."""
    ops = planted
    hyper = HYPER._replace(buffer_size=8)
    steps = 30 if policy == "dccb" else 25
    every = 8 * N if policy == "dccb" else 2 * N
    sess = serve.OnlineBandit.create(N, D, hyper, policy=policy,
                                     refresh_every=every)
    tot_r = tot_rand = 0.0
    for i in range(steps):
        ctx, k_rew = _ctx(ops, i)
        sess, _, m = serve.step(sess, k_rew, jnp.arange(N, dtype=jnp.int32),
                                ctx, _reward_fn(ops))
        tot_r += float(m.reward)
        tot_rand += float(m.rand_reward)
    assert tot_r > tot_rand * 1.05, (policy, tot_r, tot_rand)


def test_recommend_observe_halves_match_step(planted):
    """The split request/feedback halves land on the same state as the
    fused transaction when fed the realized rewards."""
    ops = planted
    sess_a = serve.OnlineBandit.create(N, D, HYPER, policy="distclub",
                                       refresh_every=2 * N)
    sess_b = sess_a
    uids = jnp.arange(N, dtype=jnp.int32)
    for i in range(3):
        ctx, k_rew = _ctx(ops, i)
        sess_a, ch_a, _ = serve.step(sess_a, k_rew, uids, ctx,
                                     _reward_fn(ops))
        ch_b = serve.recommend(sess_b, uids, ctx)
        np.testing.assert_array_equal(np.asarray(ch_a), np.asarray(ch_b))
        realized, _, _, _ = _reward_fn(ops)(k_rew, uids, ctx, ch_b)
        sess_b = serve.observe(sess_b, uids, ctx, ch_b, realized, key=k_rew)
    np.testing.assert_array_equal(np.asarray(sess_a.state.occ),
                                  np.asarray(sess_b.state.occ))
    np.testing.assert_allclose(np.asarray(sess_a.state.Minv),
                               np.asarray(sess_b.state.Minv), atol=1e-6)


def test_warm_start_from_offline_run(planted):
    """`from_offline` resumes serving from a `distclub.run` state with the
    stage-3 snapshot semantics."""
    ops = planted
    hyper = HYPER._replace(max_rounds=8)
    state, _, _ = distclub.run(ops, jax.random.PRNGKey(1), hyper,
                               n_epochs=2, d=D)
    sess = serve.OnlineBandit.from_offline(state, hyper)
    np.testing.assert_array_equal(np.asarray(sess.state.occ),
                                  np.asarray(state.lin.occ))
    ctx, k_rew = _ctx(ops, 0)
    sess, ch, m = serve.step(sess, k_rew, jnp.arange(N, dtype=jnp.int32),
                             ctx, _reward_fn(ops))
    assert int(m.interactions) == N
    # round-trip back to the offline record for checkpoint consumers
    cfg = sess.policy.cfg
    back = serve.to_distclub_state(sess.state, cfg.hyper, cfg.d)
    np.testing.assert_array_equal(np.asarray(back.lin.occ),
                                  np.asarray(sess.state.occ))


def test_bandit_service_removed_with_pointer():
    """The retired PR-4 shim fails fast with a migration pointer instead
    of silently serving the old API."""
    with pytest.raises(ImportError, match="repro.serve"):
        import repro.serve.bandit_service  # noqa: F401
