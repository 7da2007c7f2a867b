"""Distributed DistCLUB: the shared stage engine under ``shard_map``.

This module contains NO stage logic — the four stage bodies live once in
``repro.runtime.stages`` and are bound here to ``LaxCollectives`` over the
mesh axes (the single-host driver binds the same functions to
``NullCollectives``).  What remains here is pure plumbing: the sharded
state record, its partition specs, and the jit/donation wiring.

Layout (users = the distribution axis, sharded over every mesh axis
flattened — the bandit equivalent of pure data parallelism):

  Minv, b, occ, budgets      : sharded on dim 0   -> [n_local, ...]
  adj (bit-packed uint32)    : sharded rows       -> [n_local, ceil(n/32)]
  labels                     : replicated [n]     (refreshed by all_gather)
  comm_bytes                 : replicated scalar  (modeled stage-2 bytes)

Stage 1/3 are purely local (zero communication — the paper's
"embarrassingly parallel" claim is literal here).  Stage 2 is the only
communicating stage and its traffic is exactly the paper's model: one
all-gather of the n x d user vectors + occ for edge pruning, label hops
during connected components, and one psum of the (n,d,d)+(n,d) aggregates.
The adjacency never crosses the network — each shard prunes and hops its
own packed rows through the graph engine.

Environments: ANY ``EnvOps`` (synthetic / drift / logged replay) runs
here — environment tables are closed over (replicated per device; small
next to the sharded state) and sliced per shard via ``row0``, and every
random draw is keyed by GLOBAL user id, so a sharded run reproduces the
single-host run up to fp contraction order.  The env no longer lives in
the carried state (the old runtime hard-coded the synthetic generator and
carried ``theta``); the per-user cluster snapshots are likewise no longer
carried — they are epoch transients of stage 2.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core.backend import BackendConfig, GraphBackend, InteractBackend
from ..core.env_ops import EnvOps, default_synthetic_ops
from ..core.types import BanditHyper, Metrics
from ..kernels.graph import ops as graph_ops
from ..runtime import stages
from ..runtime.collectives import lax_collectives


class ShardedDistCLUB(NamedTuple):
    """State as seen *outside* shard_map (global shapes).

    §Perf iteration (bandit cell): the Gram matrix M is NOT carried — only
    its inverse is needed per interaction (UCB + Sherman-Morrison), and
    stage-2's cluster aggregation recovers M = inv(Minv) locally once per
    epoch.  §Perf iteration 2: the label-indexed cluster tables are
    stage-2 transients, not carried state.  §Unification: the environment
    (previously a carried ``theta`` + inlined synthetic sampling) moved
    into the shard-aware ``EnvOps`` closure, and the per-user cluster
    snapshots became stage-2 transients too — the carried state is now
    exactly the single-host ``DistCLUBState`` minus the recoverable
    Gram/cluster tables."""

    Minv: jnp.ndarray     # [n, d, d]   sharded dim0
    b: jnp.ndarray        # [n, d]      sharded dim0
    occ: jnp.ndarray      # [n]         sharded dim0
    adj: jnp.ndarray      # [n, ceil(n/32)] uint32 bit-packed, sharded rows
    labels: jnp.ndarray   # [n]         replicated (n i32 — cheap)
    u_rounds: jnp.ndarray  # [n] i32    sharded dim0
    c_rounds: jnp.ndarray  # [n] i32    sharded dim0
    comm_bytes: jnp.ndarray  # [] f32   replicated modeled-bytes counter


def named_shardings(mesh: Mesh, specs):
    """PartitionSpec pytree -> NamedSharding pytree over ``mesh``.  Shared
    by this runtime and the sharded serving sessions (``repro.serve``)."""
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def state_specs(axes: tuple[str, ...]) -> ShardedDistCLUB:
    s = P(axes)          # dim-0 sharded
    r = P()              # replicated
    return ShardedDistCLUB(
        Minv=s, b=s, occ=s, adj=s, labels=r,
        u_rounds=s, c_rounds=s, comm_bytes=r,
    )


def init_state(n: int, d: int, hyper: BanditHyper) -> ShardedDistCLUB:
    eye = jnp.eye(d, dtype=jnp.float32) + jnp.zeros((n, d, d), jnp.float32)
    return ShardedDistCLUB(
        Minv=eye,
        b=jnp.zeros((n, d), jnp.float32),
        occ=jnp.zeros((n,), jnp.int32),
        adj=graph_ops.init_packed_adj(n, n),
        labels=jnp.zeros((n,), jnp.int32),
        u_rounds=jnp.full((n,), hyper.sigma, jnp.int32),
        c_rounds=jnp.full((n,), hyper.sigma, jnp.int32),
        comm_bytes=jnp.zeros((), jnp.float32),
    )


def build_epoch_fn(mesh: Mesh, axes: tuple[str, ...], n: int, d: int,
                   hyper: BanditHyper,
                   backend: InteractBackend | None = None,
                   graph: GraphBackend | None = None,
                   ops: EnvOps | None = None):
    """Returns jit-able epoch(state, key) -> (state, metrics, n_clusters).

    ``metrics`` is per-scan-step ``[2 * max_rounds]`` rows (stage-1 steps
    then stage-3 steps, psum'd over shards) — the same layout one epoch of
    the single-host driver emits, so parity checks are slice-for-slice.
    ``ops`` defaults to a planted synthetic environment
    (``env_ops.default_synthetic_ops``); pass replay/drift ops to run
    those scenarios sharded.
    """
    col = lax_collectives(mesh, axes)
    if n % col.n_shards:
        raise ValueError(f"n_users={n} must divide the {col.n_shards}-way mesh")
    n_local = n // col.n_shards
    # the engines operate on the LOCAL shard inside shard_map (the graph
    # engine on [n_local, n] packed rows)
    be = backend or BackendConfig.create().interact(n_local, d,
                                                    hyper.n_candidates)
    gb = graph or BackendConfig(
        kind=be.kind, precision=be.precision,
    ).graph(n_local, n, interpret=be.interpret)
    env = ops or default_synthetic_ops(n, d, hyper.n_candidates)

    def epoch(state: ShardedDistCLUB, key: jax.Array):
        k1, k3 = jax.random.split(key)
        row0 = col.axis_index() * n_local

        # ---- stage 1: personalized rounds (local only) --------------------
        Minv, b, occ, m1 = stages.personalized_rounds(
            be, env, hyper, state.Minv, state.b, state.occ,
            state.u_rounds, k1, row0,
        )

        # ---- stage 2: the communication stage -----------------------------
        res = stages.stage2_refresh(col, gb, hyper, d, Minv, b, occ,
                                    state.adj)

        # ---- stage 3: cluster-based rounds (local; stats frozen) ----------
        Minv, b, occ, m3 = stages.cluster_rounds(
            be, env, hyper, Minv, b, occ, state.c_rounds, k3, row0,
            res.uMcinv, res.ubc, res.umean_occ,
        )

        # ---- stage 4: budget rebalancing (local; stage-2 snapshot) --------
        u_rounds, c_rounds = stages.stage4_rebalance(
            hyper, occ, res.umean_occ, state.u_rounds, state.c_rounds)

        metrics = jax.tree.map(lambda a, b_: jnp.concatenate([a, b_]),
                               m1, m3)
        metrics = jax.tree.map(lambda v: col.psum(v), metrics)

        new_state = ShardedDistCLUB(
            Minv=Minv, b=b, occ=occ, adj=res.adj, labels=res.labels,
            u_rounds=u_rounds, c_rounds=c_rounds,
            comm_bytes=state.comm_bytes + res.comm_bytes,
        )
        return new_state, metrics, res.n_clusters

    specs = state_specs(axes)
    sharded = jax.shard_map(
        epoch, mesh=mesh,
        in_specs=(specs, P()),
        out_specs=(specs, Metrics(P(), P(), P(), P()), P()),
        check_vma=False,
    )
    return sharded


def make_runtime(mesh: Mesh, axes: tuple[str, ...], n: int, d: int,
                 hyper: BanditHyper,
                 backend: InteractBackend | None = None,
                 graph: GraphBackend | None = None,
                 ops: EnvOps | None = None):
    """(init_fn, jit'd epoch_fn) pair with global-array in/out shardings.

    ``init_fn(key)`` ignores its key (kept for API stability): the initial
    bandit state is deterministic and the environment's randomness lives
    in ``ops``.
    """
    epoch = build_epoch_fn(mesh, axes, n, d, hyper, backend, graph, ops)
    shardings = named_shardings(mesh, state_specs(axes))

    def init_fn(key):
        del key
        return jax.device_put(init_state(n, d, hyper), shardings)

    epoch_jit = jax.jit(
        epoch,
        in_shardings=(shardings, NamedSharding(mesh, P())),
        out_shardings=(
            shardings,
            jax.tree.map(lambda _: NamedSharding(mesh, P()),
                         Metrics(0, 0, 0, 0)),
            NamedSharding(mesh, P()),
        ),
        donate_argnums=(0,),
    )
    return init_fn, epoch_jit
