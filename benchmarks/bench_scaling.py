"""Fig 6 analogue: scaling of the sharded DistCLUB runtime with device count.

True multi-node scaling can't be measured on one CPU core; we still verify
the *runtime mechanics* scale (same program, 1..8 host devices, fixed
problem) and report the collective-volume model per device count — the
quantity that determines scaling on a real interconnect (DistCLUB's stage-2
bytes/device FALL with device count; DCCB's gossip bytes/device do not:
that is precisely the paper's Fig 6 divergence).
"""
from __future__ import annotations

from .common import emit, run_cpu_child, save_json


_CODE = r"""
import time, jax, jax.numpy as jnp
from repro.distributed import distclub_shard
from repro.core.types import BanditHyper

from repro.launch.mesh import make_mesh

n_dev = len(jax.devices())
mesh = make_mesh((n_dev,), ("users",))
hyper = BanditHyper(sigma=8, max_rounds=16, gamma=1.6, n_candidates=20)
init_fn, epoch = distclub_shard.make_runtime(mesh, ("users",), n=2048, d=25,
                                             hyper=hyper)
state = init_fn(jax.random.PRNGKey(0))
state, m, _ = epoch(state, jax.random.PRNGKey(1))   # compile + warm
jax.block_until_ready(state)
t0 = time.perf_counter()
for i in range(3):
    state, m, _ = epoch(state, jax.random.PRNGKey(i + 2))
jax.block_until_ready(state)
print("EPOCH_S", (time.perf_counter() - t0) / 3)
"""


def main():
    rows = {}
    for n in (1, 2, 4, 8):
        stdout = run_cpu_child(_CODE, n)
        t = float(stdout.split("EPOCH_S")[1].split()[0])
        # analytic per-device comm for the paper's production scale
        n_users, d = 20_480, 25
        dclub_per_dev = 2 * (n_users // n) * (d * d + d) * 4
        dccb_per_dev = (n_users // n) * (5000 + 1) * (d * d + d) * 4
        rows[n] = {"epoch_s": t, "platform": "cpu",
                   "distclub_stage2_bytes_per_dev": dclub_per_dev,
                   "dccb_gossip_bytes_per_dev": dccb_per_dev}
        emit(f"fig6_scaling_dev{n}", 1e6 * t,
             f"comm/dev: distclub={dclub_per_dev/1e6:.1f}MB "
             f"dccb={dccb_per_dev/1e9:.1f}GB")
    save_json("scaling", rows)
    return rows


if __name__ == "__main__":
    main()
