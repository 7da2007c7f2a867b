"""Online-serving transaction benchmark + per-request HBM accounting.

Times the full jit-compiled `OnlineBandit.step` transaction (score ->
fused choose -> reward -> duplicate-safe fold -> refresh cond) for the
distclub policy at serving shapes, two engines:

  reference   the jnp engine (`REPRO_BACKEND=reference`)
  fused       the interaction-engine kernels; off-TPU this is explicitly
              the interpret-mode Pallas backend (kernel-path validation,
              NOT a wall-clock claim — see bench_interact's rationale),
              flagged per record via `fused_backend`/`wallclock_comparable`.

The per-request HBM model extends bench_interact's per-round model
(serving is M-free, like the sharded runtime) with the serving layer's
extra row traffic: the beta-heuristic gathers of the frozen cluster
snapshot (`uMcinv` d^2 + `ubc` d + `umean_occ` 1 words) plus the
scatter-back of the updated `Minv`/`b` rows already counted by the
update sweep.  The refresh itself amortizes over `refresh_every`
requests and is excluded (stage-2's model lives in bench_graph).

Also records an 8-device sharded serving row (subprocess host-platform
mesh): the same transaction under shard_map, reference engine.

Writes BENCH_serve.json at the repo root (tracked from PR 4 onward).
"""
from __future__ import annotations

import json
import pathlib

import jax
import jax.numpy as jnp

from repro import serve
from repro.core import env, env_ops
from repro.core.types import BanditHyper

from .bench_interact import hbm_words_fused, hbm_words_reference
from .common import emit, timed, run_cpu_child

ROOT = pathlib.Path(__file__).resolve().parents[1]

# (n_users, batch) at the serving feature/candidate shape d=32, K=64.
# QUICK_SHAPES stays a subset of FULL_SHAPES: check_regression matches
# rows by shape identity and treats a vanished baseline row as a failure,
# so a full-mode run must cover every quick-mode (baseline) row.
FULL_SHAPES = [(1024, 256), (4096, 256), (16384, 512)]
QUICK_SHAPES = [(1024, 256)]
D, K = 32, 64

_SHARDED_CODE = r"""
import time, jax, jax.numpy as jnp
from repro import serve
from repro.core import env, env_ops
from repro.core.types import BanditHyper

N, B, D, K = {n}, {batch}, 32, 64
hyper = BanditHyper(alpha=0.05, gamma=1.5, n_candidates=K)
e, _ = env.make_synthetic_env(jax.random.PRNGKey(0), N, D, 8, K)
ops = env_ops.synthetic_ops(e)
theta = e.theta

def reward_fn(key, uids, ctx, choice):
    return env.step_rewards(key, theta[uids], ctx, choice)

from repro.launch.mesh import make_mesh
mesh = make_mesh((8,), ("users",))
session = serve.OnlineBandit.sharded(mesh, N, D, hyper, policy="distclub",
                                     refresh_every=0, backend="reference")
ctx = jax.random.normal(jax.random.PRNGKey(1), (B, K, D))
ctx = ctx / jnp.linalg.norm(ctx, axis=-1, keepdims=True)
uids = jax.random.permutation(jax.random.PRNGKey(2), N)[:B].astype(jnp.int32)
session, c, m = serve.step(session, jax.random.PRNGKey(3), uids, ctx,
                           reward_fn)               # compile + warm
jax.block_until_ready(c)
t0 = time.perf_counter()
REP = 5
for i in range(REP):
    session, c, m = serve.step(session, jax.random.PRNGKey(4 + i), uids,
                               ctx, reward_fn)
jax.block_until_ready(c)
print("SHARD_STEP_US", 1e6 * (time.perf_counter() - t0) / REP)
"""


def serve_words(d: int, K: int, fused: bool) -> int:
    """f32 words of HBM traffic per request (M-free engine + the
    clustered policy's frozen-snapshot gathers)."""
    base = (hbm_words_fused if fused else hbm_words_reference)(
        d, K, with_M=False)
    snapshot = d * d + d + 1            # uMcinv, ubc, umean_occ rows
    return base + snapshot


def _session(n, kind, interpret):
    hyper = BanditHyper(alpha=0.05, gamma=1.5, n_candidates=K)
    return serve.OnlineBandit.create(n, D, hyper, policy="distclub",
                                     refresh_every=0, backend=kind,
                                     interpret=interpret)


def bench_shape(n, batch, repeats=3):
    e, _ = env.make_synthetic_env(jax.random.PRNGKey(0), n, D, 8, K)
    theta = e.theta

    def reward_fn(key, uids, ctx, choice):
        return env.step_rewards(key, theta[uids], ctx, choice)

    ctx = jax.random.normal(jax.random.PRNGKey(1), (batch, K, D))
    ctx = ctx / jnp.linalg.norm(ctx, axis=-1, keepdims=True)
    uids = jax.random.permutation(
        jax.random.PRNGKey(2), n)[:batch].astype(jnp.int32)
    key = jax.random.PRNGKey(3)

    on_tpu = jax.default_backend() == "tpu"
    results = {}
    for col, (kind, interp, reps) in {
        "reference": ("reference", None, repeats),
        # like bench_interact: off-TPU the fused column must exercise the
        # kernel path (interpret mode), never silently fall back
        "fused": ("pallas", None if on_tpu else True,
                  repeats if on_tpu else 1),
    }.items():
        sess = _session(n, kind, interp)
        sess, c, _ = serve.step(sess, key, uids, ctx, reward_fn)  # compile
        jax.block_until_ready(c)

        def one_step(sess=sess):
            s2, c2, _ = serve.step(sess, key, uids, ctx, reward_fn)
            return c2

        secs, _ = timed(one_step, repeats=reps)
        results[col] = 1e6 * secs

    rec = {
        "n_users": n, "batch": batch, "d": D, "K": K,
        "policy": "distclub",
        "fused_backend": "pallas" if on_tpu else "pallas_interpret",
        "wallclock_comparable": on_tpu,
        "reference_us": results["reference"],
        "fused_us": results["fused"],
        "reference_req_per_s": batch / (results["reference"] * 1e-6),
        "hbm_bytes_per_request_reference": 4 * serve_words(D, K, False),
        "hbm_bytes_per_request_fused": 4 * serve_words(D, K, True),
        "hbm_traffic_ratio": serve_words(D, K, False)
        / serve_words(D, K, True),
    }
    emit(f"serve_step_n{n}_B{batch}_reference", rec["reference_us"],
         f"req/s={rec['reference_req_per_s']:.0f}")
    emit(f"serve_step_n{n}_B{batch}_fused", rec["fused_us"],
         f"hbm_ratio={rec['hbm_traffic_ratio']:.2f}x")
    return rec


def _sharded_row(n, batch):
    stdout = run_cpu_child(_SHARDED_CODE.format(n=n, batch=batch), 8)
    us = float(stdout.split("SHARD_STEP_US")[1].split()[0])
    emit(f"serve_step_sharded8_n{n}_B{batch}", us,
         f"req/s={batch / (us * 1e-6):.0f}")
    return {"n_users": n, "batch": batch, "step_us": us,
            "req_per_s": batch / (us * 1e-6), "platform": "cpu"}


def main(quick: bool = False):
    shapes = QUICK_SHAPES if quick else FULL_SHAPES
    records = [bench_shape(n, b, repeats=2 if quick else 3)
               for (n, b) in shapes]
    payload = {
        "mode": "quick" if quick else "full",
        "jax_backend": jax.default_backend(),
        "fused_wallclock_note": (
            "fused_us is a compiled TPU kernel only where "
            "wallclock_comparable is true; on CPU runners it is the "
            "Pallas interpreter (kernel-path validation, not a speed "
            "claim)"),
        "hbm_model_note": (
            "per-request words: bench_interact per-round model with "
            "with_M=False (serving is M-free) + d^2+d+1 frozen-snapshot "
            "gathers; refresh amortizes over refresh_every and is "
            "modeled in bench_graph"),
        "shapes": records,
        "sharded_8dev": _sharded_row(*shapes[0]),
        "min_traffic_ratio": min(r["hbm_traffic_ratio"] for r in records),
    }
    (ROOT / "BENCH_serve.json").write_text(json.dumps(payload, indent=1))
    return payload


if __name__ == "__main__":
    main()
