"""Single-host vs sharded parity through the unified stage engine.

Both drivers bind the SAME stage bodies (``repro.runtime.stages``) — the
single-host driver to ``NullCollectives``, the sharded one to ``lax``
collectives on an 8-device host-platform mesh — and every environment
draw is keyed by GLOBAL user id, so from one seed the two runs must
agree:

  * exactly on everything integer-valued or per-user (interactions,
    realized rewards, occ, pruned adjacency bits, CC labels, cluster
    counts, Minv/b state): per-user math is identical elementwise and the
    graph engine is bit-exact across row shardings;
  * within fp-contraction tolerance on the float metric sums (the psum of
    per-shard partials reassociates the additions) — observed ~1e-6 at
    this scale, asserted at 1e-4.

The only cross-user float contraction feeding back into decisions is the
stage-2 psum of cluster aggregates; at test scale it has never flipped an
argmax (state equality below is exact), and if a future change makes that
flip legitimately possible the exact asserts are the tripwire.

Also here: the replay-backed and drift scenarios running under shard_map
(the unification's point — the old sharded runtime hard-coded the
synthetic generator), with per-stage drift parity.
"""
from test_distributed import _run_with_devices


def test_distclub_single_host_vs_sharded_parity():
    out = _run_with_devices("""
        import numpy as np
        import jax
        from repro.core import distclub, env, env_ops
        from repro.core.types import BanditHyper
        from repro.distributed import distclub_shard

        N, D, K, E = 64, 8, 10, 3
        hyper = BanditHyper(sigma=8, max_rounds=16, gamma=1.5,
                            n_candidates=K)
        e, _ = env.make_synthetic_env(jax.random.PRNGKey(0), N, D, 4, K)
        ops = env_ops.synthetic_ops(e)

        s1, m1, c1 = distclub.run(ops, jax.random.PRNGKey(1), hyper,
                                  n_epochs=E, d=D)
        R = 2 * hyper.max_rounds
        m1 = jax.tree.map(lambda v: np.asarray(v).reshape(E, R), m1)

        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("users",))
        init_fn, epoch = distclub_shard.make_runtime(
            mesh, ("users",), N, D, hyper, ops=ops)
        st = init_fn(jax.random.PRNGKey(0))
        # the single-host run splits its key once per epoch; feed the
        # sharded epochs the same schedule
        keys = jax.random.split(jax.random.PRNGKey(1), E)
        ms, nclus = [], []
        for k in keys:
            st, mm, nc = epoch(st, k)
            ms.append(jax.tree.map(np.asarray, mm))
            nclus.append(int(nc))
        ms = jax.tree.map(lambda *xs: np.stack(xs), *ms)

        # exact: integer metrics, realized rewards, cluster counts
        np.testing.assert_array_equal(ms.interactions, m1.interactions)
        np.testing.assert_array_equal(ms.reward, m1.reward)
        np.testing.assert_array_equal(np.asarray(c1), np.asarray(nclus))
        # fp-contraction tolerance: psum reassociates the float sums
        np.testing.assert_allclose(ms.regret, m1.regret, atol=1e-4)
        np.testing.assert_allclose(ms.rand_reward, m1.rand_reward,
                                   atol=1e-4)
        # exact: per-user state and the stage-2 graph
        np.testing.assert_array_equal(np.asarray(st.occ),
                                      np.asarray(s1.lin.occ))
        np.testing.assert_array_equal(np.asarray(st.labels),
                                      np.asarray(s1.graph.labels))
        np.testing.assert_array_equal(np.asarray(st.adj),
                                      np.asarray(s1.graph.adj))
        np.testing.assert_allclose(np.asarray(st.Minv),
                                   np.asarray(s1.lin.Minv), atol=1e-6)
        np.testing.assert_allclose(np.asarray(st.b),
                                   np.asarray(s1.lin.b), atol=1e-6)
        # the comm model is shared code, but assert the accounting wiring
        assert float(st.comm_bytes) == float(s1.comm_bytes)
        print("PARITY-OK")
    """)
    assert "PARITY-OK" in out


def test_drift_scenario_per_stage_parity_sharded():
    """The non-stationary scenario through both drivers: per-stage metric
    slices (stage-1 rows vs stage-3 rows of each epoch) agree between the
    single-host and 8-way sharded runs."""
    out = _run_with_devices("""
        import numpy as np
        import jax
        from repro.core import distclub, env, env_ops
        from repro.core.types import BanditHyper
        from repro.distributed import distclub_shard

        N, D, K, E = 64, 8, 10, 4
        hyper = BanditHyper(sigma=8, max_rounds=16, gamma=1.5,
                            n_candidates=K)
        denv, _ = env.make_drift_env(jax.random.PRNGKey(0), N, D, 4, K,
                                     drift_period=24, n_phases=3)
        ops = env_ops.drift_ops(denv)

        s1, m1, c1 = distclub.run(ops, jax.random.PRNGKey(2), hyper,
                                  n_epochs=E, d=D)
        R = hyper.max_rounds
        m1 = jax.tree.map(lambda v: np.asarray(v).reshape(E, 2 * R), m1)

        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("users",))
        init_fn, epoch = distclub_shard.make_runtime(
            mesh, ("users",), N, D, hyper, ops=ops)
        st = init_fn(jax.random.PRNGKey(0))
        keys = jax.random.split(jax.random.PRNGKey(2), E)
        ms = []
        for k in keys:
            st, mm, _ = epoch(st, k)
            ms.append(jax.tree.map(np.asarray, mm))
        ms = jax.tree.map(lambda *xs: np.stack(xs), *ms)

        for stage, sl in (("stage1", slice(0, R)), ("stage3", slice(R, None))):
            np.testing.assert_array_equal(
                ms.interactions[:, sl], m1.interactions[:, sl])
            np.testing.assert_array_equal(
                ms.reward[:, sl], m1.reward[:, sl])
            np.testing.assert_allclose(
                ms.regret[:, sl], m1.regret[:, sl], atol=1e-4)
        # the drift actually bites inside the horizon: some user crossed
        # a phase boundary (occ >= drift_period)
        assert int(np.asarray(st.occ).max()) >= 24
        print("DRIFT-PARITY-OK")
    """)
    assert "DRIFT-PARITY-OK" in out


def test_replay_scenario_runs_sharded():
    """Logged-replay EnvOps under shard_map: per-user queues sliced by
    row0, learner beats random, metrics match the single-host replay run
    exactly on integers."""
    out = _run_with_devices("""
        import numpy as np
        import jax
        from repro.core import distclub
        from repro.core.types import BanditHyper
        from repro.data.datasets import DatasetSpec, make_env
        from repro.distributed import distclub_shard

        spec = DatasetSpec("tiny", 4096, 64, 8, 4, n_candidates=10)
        ops, _ = make_env(spec, seed=3, kind="replay")
        hyper = BanditHyper(sigma=8, max_rounds=16, gamma=1.5,
                            n_candidates=10)
        E = 3
        s1, m1, _ = distclub.run(ops, jax.random.PRNGKey(4), hyper,
                                 n_epochs=E, d=8)

        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("users",))
        init_fn, epoch = distclub_shard.make_runtime(
            mesh, ("users",), 64, 8, hyper, ops=ops)
        st = init_fn(jax.random.PRNGKey(0))
        keys = jax.random.split(jax.random.PRNGKey(4), E)
        tot_r = tot_rand = tot_t = 0.0
        rew = []
        for k in keys:
            st, mm, _ = epoch(st, k)
            rew.append(np.asarray(mm.reward))
            tot_r += float(mm.reward.sum())
            tot_rand += float(mm.rand_reward.sum())
            tot_t += int(mm.interactions.sum())
        assert tot_t == 64 * 2 * hyper.sigma * E
        assert tot_r > tot_rand * 1.05, (tot_r, tot_rand)
        np.testing.assert_array_equal(
            np.concatenate(rew), np.asarray(m1.reward))
        print("REPLAY-SHARD-OK", tot_r / tot_rand)
    """)
    assert "REPLAY-SHARD-OK" in out
