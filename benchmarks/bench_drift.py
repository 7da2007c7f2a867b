"""Non-stationary (drift) scenario benchmark through the unified engine.

The abstract's motivating regime — "content popularity can change
rapidly" — as a tracked workload: DistCLUB runs on ``DriftEnv`` (cluster
centroids re-draw every ``drift_period`` per-user interactions) and we
record, per phase, the reward/random ratio plus the end-to-end epoch
timing.  A healthy learner shows the signature dip-and-recover: the
ratio drops right after each re-draw and climbs back within the phase.

Two scenario rows:

  single_host  the engine with null collectives (this process)
  sharded_8dev the SAME stage functions under shard_map on an 8-device
               host-platform mesh (subprocess; the drift EnvOps is
               shard-aware, so this is one ``ops=`` argument away)

Writes BENCH_drift.json at the repo root (tracked from PR 3 onward).
"""
from __future__ import annotations

import json
import pathlib

import jax

from repro.core import distclub, env, env_ops
from repro.core.types import BanditHyper

from .common import timed, emit, run_cpu_child

ROOT = pathlib.Path(__file__).resolve().parents[1]

D, K = 16, 10
HYPER = BanditHyper(sigma=8, max_rounds=16, gamma=1.5, n_candidates=K)
N_PHASES = 3
# full: 3 epochs (of 2*sigma=16 interactions/user) per phase; quick halves
# the user count and runs 2 epochs/phase — same dip-and-recover signal,
# well under a minute on one core.
FULL = dict(n=256, clusters=8, drift_period=48, epochs=9)
QUICK = dict(n=128, clusters=8, drift_period=32, epochs=6)

_SHARDED_CODE = r"""
import time, jax
from repro.core import env, env_ops
from repro.core.types import BanditHyper
from repro.distributed import distclub_shard

N, D, K, CLUSTERS = {n}, 16, 10, {clusters}
EPOCHS = {epochs}
hyper = BanditHyper(sigma=8, max_rounds=16, gamma=1.5, n_candidates=K)
denv, _ = env.make_drift_env(jax.random.PRNGKey(0), N, D, CLUSTERS, K,
                             drift_period={drift_period}, n_phases=3)
ops = env_ops.drift_ops(denv)
from repro.launch.mesh import make_mesh
mesh = make_mesh((8,), ("users",))
init_fn, epoch = distclub_shard.make_runtime(mesh, ("users",), N, D, hyper,
                                             ops=ops)
state = init_fn(jax.random.PRNGKey(0))
keys = jax.random.split(jax.random.PRNGKey(1), EPOCHS)
state, m, _ = epoch(state, keys[0])          # compile + warm
jax.block_until_ready(state)
t0 = time.perf_counter()
tot_r = tot_rand = 0.0
for k in keys[1:]:
    state, m, _ = epoch(state, k)
    tot_r += float(m.reward.sum()); tot_rand += float(m.rand_reward.sum())
jax.block_until_ready(state)
print("SHARD_EPOCH_S", (time.perf_counter() - t0) / (EPOCHS - 1),
      "RATIO", tot_r / tot_rand)
"""


def _phase_ratios(metrics, epochs):
    """Reward/random ratio per drift phase (epoch-granular split)."""
    per_epoch = metrics.reward.shape[0] // epochs
    ratios = []
    for p in range(N_PHASES):
        lo = p * (epochs // N_PHASES) * per_epoch
        hi = (p + 1) * (epochs // N_PHASES) * per_epoch
        r = float(metrics.reward[lo:hi].sum())
        rnd = float(metrics.rand_reward[lo:hi].sum())
        ratios.append(r / max(rnd, 1e-9))
    return ratios


def main(quick: bool = False):
    cfg = QUICK if quick else FULL
    n, epochs = cfg["n"], cfg["epochs"]
    denv, _ = env.make_drift_env(jax.random.PRNGKey(0), n, D,
                                 cfg["clusters"], K,
                                 drift_period=cfg["drift_period"],
                                 n_phases=N_PHASES)
    ops = env_ops.drift_ops(denv)
    secs, (state, metrics, nclu) = timed(
        distclub.run, ops, jax.random.PRNGKey(1), HYPER, epochs, D)
    ratios = _phase_ratios(metrics, epochs)
    payload = {
        "scenario": {
            "n_users": n, "d": D, "n_clusters": cfg["clusters"],
            "drift_period": cfg["drift_period"], "n_phases": N_PHASES,
            "epochs": epochs, "quick": quick,
        },
        "single_host": {
            "total_s": secs,
            "epoch_s": secs / epochs,
            "reward_over_random_per_phase": ratios,
            "final_clusters": int(nclu[-1]),
        },
    }
    emit("drift_single_host_epoch", 1e6 * secs / epochs,
         f"reward/rand per phase {['%.3f' % r for r in ratios]}")

    code = _SHARDED_CODE.format(n=n, clusters=cfg["clusters"],
                                drift_period=cfg["drift_period"],
                                epochs=epochs)
    parts = run_cpu_child(code, 8).split()
    payload["sharded_8dev"] = {
        "epoch_s": float(parts[1]),
        "reward_over_random": float(parts[3]),
        "platform": "cpu",
    }
    emit("drift_sharded_8dev_epoch", 1e6 * float(parts[1]),
         f"reward/rand {float(parts[3]):.3f}")

    (ROOT / "BENCH_drift.json").write_text(json.dumps(payload, indent=1))


if __name__ == "__main__":
    main()
