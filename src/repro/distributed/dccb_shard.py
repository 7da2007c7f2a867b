"""Distributed DCCB baseline under shard_map — gossip via collective_permute.

The paper's scaling argument (Fig 6) needs DCCB runnable on the same mesh
as DistCLUB.  Users are sharded as in ``distclub_shard``; the per-epoch
structure is L lockstep interaction steps followed by one gossip round.

The interaction steps route through the SAME shared round protocol as the
DistCLUB stages (``runtime.stages.interaction_rounds``): DCCB supplies a
lagged-Gram ``score_fn`` and a FIFO-buffer ``update_fn``, and the
environment is any shard-aware ``EnvOps`` (synthetic / drift / replay) —
the old runtime inlined the synthetic generator and carried ``theta``.
Per-user PRNG keying means a sharded DCCB run draws the same
contexts/rewards as the single-host ``repro.core.dccb`` driver.

Gossip mapping: the paper pairs each user with a random connected peer.
On a mesh, cross-shard random pairing is an all-to-all; the standard
hardware-shaped equivalent is a *permuted-neighbor* exchange — each shard
sends its users' (buffer, current) payloads to the next shard over the
ring (``collective_permute``, exactly one ICI hop) and pairs its users
with the arrivals.  Information still spreads one hop per round (the same
rate as the paper's random gossip in expectation); the per-round traffic
IS the paper's Table-4 objection: (L+1)(d^2+d) floats per user, which this
implementation ships for real.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core import clustering, linucb
from ..core.backend import BackendConfig, InteractBackend
from ..core.env_ops import EnvOps, default_synthetic_ops
from ..core.types import BanditHyper, Metrics
from ..runtime import stages
from ..runtime.collectives import lax_collectives


class ShardedDCCB(NamedTuple):
    Mw: jnp.ndarray       # [n, d, d] current (lagged) Gram
    bw: jnp.ndarray       # [n, d]
    xbuf: jnp.ndarray     # [n, L, d]   FIFO of pending update contexts
    rbuf: jnp.ndarray     # [n, L]      ... and rewards
    occ: jnp.ndarray      # [n] i32
    comm_bytes: jnp.ndarray  # [] f32


def state_specs(axes) -> ShardedDCCB:
    s = P(axes)
    return ShardedDCCB(Mw=s, bw=s, xbuf=s, rbuf=s, occ=s, comm_bytes=P())


def init_state(n, d, L) -> ShardedDCCB:
    eye = jnp.eye(d, dtype=jnp.float32) + jnp.zeros((n, d, d), jnp.float32)
    return ShardedDCCB(
        Mw=eye, bw=jnp.zeros((n, d), jnp.float32),
        xbuf=jnp.zeros((n, L, d), jnp.float32),
        rbuf=jnp.zeros((n, L), jnp.float32),
        occ=jnp.zeros((n,), jnp.int32),
        comm_bytes=jnp.zeros((), jnp.float32),
    )


def build_epoch_fn(mesh: Mesh, axes, n: int, d: int, L: int,
                   hyper: BanditHyper,
                   ops: EnvOps | None = None,
                   backend: InteractBackend | None = None):
    col = lax_collectives(mesh, axes)
    n_shards = col.n_shards
    assert n % n_shards == 0
    n_local = n // n_shards
    be = backend or BackendConfig.create().interact(n_local, d,
                                                    hyper.n_candidates)
    env = ops or default_synthetic_ops(n, d, hyper.n_candidates)

    def epoch(state: ShardedDCCB, key: jax.Array):
        # same key schedule as the single-host driver (dccb._run splits
        # each epoch key into interaction/gossip halves), so both drivers
        # draw identical per-user env streams from one epoch key; the ring
        # gossip here is deterministic, so its key half goes unused.
        k_int, _ = jax.random.split(key)
        row0 = col.axis_index() * n_local

        # ---- L lockstep interactions via the shared round protocol ------
        def score_lagged(carry):
            Mw, bw, *_ = carry
            Minv = jnp.linalg.inv(Mw)
            return linucb.user_vector(Minv, bw), Minv

        def update_buffered(carry, slot, x, realized, mask):
            del mask                            # lockstep: all users live
            Mw, bw, xbuf, rbuf, occ = carry
            # pop oldest into current; push the new update
            x_old = xbuf[:, slot]
            r_old = rbuf[:, slot]
            Mw = Mw + jnp.einsum("ni,nj->nij", x_old, x_old)
            bw = bw + r_old[:, None] * x_old
            xbuf = xbuf.at[:, slot].set(x)
            rbuf = rbuf.at[:, slot].set(realized)
            return (Mw, bw, xbuf, rbuf, occ + 1)

        carry0 = (state.Mw, state.bw, state.xbuf, state.rbuf, state.occ)
        (Mw, bw, xbuf, rbuf, occ), metrics = stages.interaction_rounds(
            be, env, hyper, k_int, carry0, row0=row0, n_steps=L,
            occ_of=lambda c: c[4], score_fn=score_lagged,
            update_fn=update_buffered, budget=None,
        )
        metrics = jax.tree.map(lambda v: col.psum(v), metrics)

        # ---- gossip: one-hop ring exchange of (buffer + current) --------
        perm = [(i, (i + 1) % n_shards) for i in range(n_shards)]

        def ring(a):
            return jax.lax.ppermute(a, axes, perm)

        pM, pb = ring(Mw), ring(bw)
        pxb, prb = ring(xbuf), ring(rbuf)
        pocc = ring(occ)

        # paper's update: compare local vs peer estimates; average when
        # neighborhoods agree (here: always merge-average — the ring pairs
        # each user with one peer, the complete-graph early phase)
        M_loc = Mw + jnp.einsum("nld,nle->nde", xbuf, xbuf)
        b_loc = bw + jnp.einsum("nl,nld->nd", rbuf, xbuf)
        Mp_loc = pM + jnp.einsum("nld,nle->nde", pxb, pxb)
        bp_loc = pb + jnp.einsum("nl,nld->nd", prb, pxb)
        w = jnp.linalg.solve(M_loc, b_loc[..., None])[..., 0]
        v = jnp.linalg.solve(Mp_loc, bp_loc[..., None])[..., 0]
        dist = jnp.linalg.norm(w - v, axis=-1)
        width = clustering.cb_width(occ) + clustering.cb_width(pocc)
        similar = dist < hyper.gamma * width

        def mix(a, pa):
            sim = similar.reshape((-1,) + (1,) * (a.ndim - 1))
            return jnp.where(sim, 0.5 * (a + pa), a)

        Mw = mix(Mw, pM)
        bw = mix(bw, pb)
        xbuf = mix(xbuf, pxb)
        rbuf = mix(rbuf, prb)

        per_user = (L + 1) * (d * d + d) * 4.0
        comm = state.comm_bytes + jnp.float32(n) * per_user
        return ShardedDCCB(Mw, bw, xbuf, rbuf, occ, comm), metrics

    specs = state_specs(axes)
    return jax.shard_map(
        epoch, mesh=mesh,
        in_specs=(specs, P()),
        out_specs=(specs, Metrics(P(), P(), P(), P())),
        check_vma=False,
    )


def make_runtime(mesh: Mesh, axes, n: int, d: int, L: int,
                 hyper: BanditHyper, ops: EnvOps | None = None):
    """(init_fn, jit'd epoch_fn); ``init_fn(key)`` ignores its key (the
    environment's randomness lives in ``ops``).  ``metrics`` out of the
    epoch is per-step ``[L]`` rows, like the single-host driver."""
    epoch = build_epoch_fn(mesh, axes, n, d, L, hyper, ops)
    shardings = jax.tree.map(
        lambda s: NamedSharding(mesh, s), state_specs(axes),
        is_leaf=lambda x: isinstance(x, P))

    def init_fn(key):
        del key
        return jax.device_put(init_state(n, d, L), shardings)

    epoch_jit = jax.jit(
        epoch,
        in_shardings=(shardings, NamedSharding(mesh, P())),
        out_shardings=(shardings, jax.tree.map(
            lambda _: NamedSharding(mesh, P()), Metrics(0, 0, 0, 0))),
        donate_argnums=(0,),
    )
    return init_fn, epoch_jit
