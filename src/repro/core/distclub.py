"""DistCLUB single-host driver: the stage engine run with null collectives.

The four stage bodies (paper Listing 3) live ONCE in
``repro.runtime.stages`` — this module binds them to
``NullCollectives`` (one shard, every collective the identity, row0 = 0)
and adapts them to the public ``DistCLUBState`` record that the serving
layer, the checkpoint manager and the tests consume.  The sharded runtime
(``repro.distributed.distclub_shard``) binds the *same* stage functions to
``lax`` collectives inside ``shard_map``; the two drivers cannot drift
because there is no second stage body.

Stage 1  user-based LinUCB rounds        — all users advance in parallel,
                                           masked by ``u_rounds``.
Stage 2  network update + clustering     — edge pruning, connected
                                           components, tree-reduced
                                           cluster statistics.
Stage 3  cluster-based UCB rounds        — as stage 1 but scoring uses
                                           the FROZEN stage-2 cluster
                                           snapshots, except the paper's
                                           beta-heuristic users.
Stage 4  budget rebalancing              — delta = (occ - mean_occ)/2
                                           where ``mean_occ`` is the
                                           STAGE-2 snapshot (same value
                                           stage 3 reads) — unified with
                                           the sharded semantics.

State notes: the engine is M-free (the hot loop carries only ``Minv`` —
Sherman-Morrison + UCB never need the Gram itself).  ``lin.M`` is left
untouched by stages 1/3 (stage 2 recovers M from Minv internally before
the tree reduction); ``run`` refreshes it once after the epoch scan via
:func:`refresh_gram` for the consumers that want the Gram (serving layer
aggregates, checkpoints).  ``clusters.seen`` is the frozen
stage-2 snapshot — stage 3 no longer advances it (the old single-host
behavior that made stage 4 diverge from the sharded runtime).

Parallelism note: the paper serializes interactions *within* a cluster in
stage 3 only because its Spark tasks mutate shared cluster objects.  Here
the cluster statistics are frozen between stage-2 refreshes (exactly the
paper's "lazy" semantics) and only per-user statistics mutate, so every
user advances in parallel without conflicts; cross-step ordering per user
is preserved by the scan.  The regret analysis in paper §4 covers this
schedule — the same lazy-update argument used to justify DCCB's buffering.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from . import clustering, linucb
from ..kernels.spdinv.ops import spd_inverse
from ..runtime import stages
from ..runtime.collectives import NullCollectives
from .backend import BackendConfig, GraphBackend, InteractBackend
from .env_ops import EnvOps
from .types import (BanditHyper, ClusterStats, DistCLUBState, GraphState,
                    Metrics)

_NULL = NullCollectives()


def init_state(n_users: int, d: int, hyper: BanditHyper) -> DistCLUBState:
    lin = linucb.init_linucb(n_users, d)
    graph = clustering.init_graph(n_users)
    labels = jnp.zeros((n_users,), jnp.int32)  # one big cluster initially
    stats = clustering.cluster_stats(labels, lin.M, lin.b, d)
    rounds = jnp.full((n_users,), hyper.sigma, jnp.int32)
    return DistCLUBState(
        lin=lin,
        graph=graph._replace(labels=labels),
        clusters=stats,
        u_rounds=rounds,
        c_rounds=rounds,
        comm_bytes=jnp.zeros((), jnp.float32),
    )


def stage2_comm_bytes(n: int, d: int) -> int:
    """Modeled network bytes per stage-2 refresh — the single source of
    truth lives with the stage body (``runtime.stages``)."""
    return stages.stage2_comm_bytes(n, d)


def _default_backend(state: DistCLUBState, hyper: BanditHyper):
    n, d = state.lin.b.shape
    return BackendConfig.create().interact(n, d, hyper.n_candidates)


def _with_lin(state: DistCLUBState, Minv, b, occ) -> DistCLUBState:
    """Fold engine outputs back into the public record.

    ``lin.M`` is deliberately NOT touched here: nothing inside an epoch
    reads it (stage 2 recovers M from Minv itself), so recomputing it per
    stage would be two wasted n x d^3 batched inversions per epoch inside
    the scan.  Use :func:`refresh_gram` (``run`` does, once, after the
    epoch scan) when a coherent Gram is needed — serving aggregates,
    checkpoints."""
    lin = state.lin._replace(Minv=Minv, b=b, occ=occ)
    return state._replace(lin=lin)


def serving_snapshot(state: DistCLUBState):
    """Per-user cluster snapshots ``(uMcinv, ubc, umean_occ)`` gathered
    from the label-indexed stage-2 tables — the FROZEN values stage 3's
    beta heuristic reads, and what the serving layer (``repro.serve``)
    carries between refreshes."""
    labels = state.graph.labels
    stats = state.clusters
    return (stats.Mcinv[labels], stats.bc[labels],
            stages.snapshot_mean_occ(stats.seen, stats.size, labels))


def refresh_gram(state: DistCLUBState) -> DistCLUBState:
    """Recover ``lin.M = inv(lin.Minv)`` (exact up to the accumulated
    Sherman-Morrison fp error) for consumers of the Gram itself."""
    lin = state.lin._replace(M=spd_inverse(state.lin.Minv))
    return state._replace(lin=lin)


def stage1(state: DistCLUBState, ops: EnvOps, key: jax.Array,
           hyper: BanditHyper, backend: InteractBackend | None = None):
    """User-based rounds: embarrassingly parallel across users."""
    be = backend or _default_backend(state, hyper)
    Minv, b, occ, metrics = stages.personalized_rounds(
        be, ops, hyper, state.lin.Minv, state.lin.b, state.lin.occ,
        state.u_rounds, key, row0=0,
    )
    return _with_lin(state, Minv, b, occ), metrics


def stage2(state: DistCLUBState, hyper: BanditHyper, d: int,
           graph: GraphBackend | None = None) -> DistCLUBState:
    """Network update, clustering, cluster statistics (the comm stage)."""
    gb = graph or BackendConfig.create().graph(state.graph.labels.shape[0])
    res = stages.stage2_refresh(
        _NULL, gb, hyper, d,
        state.lin.Minv, state.lin.b, state.lin.occ, state.graph.adj,
    )
    with jax.named_scope("stage2"):
        with jax.named_scope("cluster_inverse"):
            Mcinv = spd_inverse(res.Mc)
        stats = ClusterStats(Mc=res.Mc, Mcinv=Mcinv, bc=res.bc,
                             size=res.size, seen=res.seen)
        return state._replace(
            graph=GraphState(adj=res.adj, labels=res.labels),
            clusters=stats,
            comm_bytes=state.comm_bytes + res.comm_bytes,
        )


def stage3(state: DistCLUBState, ops: EnvOps, key: jax.Array,
           hyper: BanditHyper, backend: InteractBackend | None = None):
    """Cluster-based rounds with the beta personalization heuristic.

    The per-user cluster snapshots are gathered from the stage-2 tables
    and stay FROZEN for the whole stage — including ``clusters.seen``,
    which this stage no longer advances (stage 4 reads the same stage-2
    snapshot in both runtimes)."""
    be = backend or _default_backend(state, hyper)
    with jax.named_scope("stage3"):
        uMcinv, ubc, umean_occ = serving_snapshot(state)
    Minv, b, occ, metrics = stages.cluster_rounds(
        be, ops, hyper, state.lin.Minv, state.lin.b, state.lin.occ,
        state.c_rounds, key, 0, uMcinv, ubc, umean_occ,
    )
    return _with_lin(state, Minv, b, occ), metrics


def stage4(state: DistCLUBState, hyper: BanditHyper) -> DistCLUBState:
    """Rebalance per-user budgets between personalized / cluster rounds
    (against the stage-2 mean-occ snapshot — see the engine docstring)."""
    with jax.named_scope("stage4"):
        umean_occ = stages.snapshot_mean_occ(
            state.clusters.seen, state.clusters.size, state.graph.labels)
    u_rounds, c_rounds = stages.stage4_rebalance(
        hyper, state.lin.occ, umean_occ, state.u_rounds, state.c_rounds)
    return state._replace(u_rounds=u_rounds, c_rounds=c_rounds)


def run(
    ops: EnvOps,
    key: jax.Array,
    hyper: BanditHyper,
    n_epochs: int,
    d: int,
    backend: InteractBackend | None = None,
    graph: GraphBackend | None = None,
) -> tuple[DistCLUBState, Metrics, jnp.ndarray]:
    """Run ``n_epochs`` of the four-stage loop.

    ``backend`` selects the interaction engine and ``graph`` the stage-2
    graph engine (default: REPRO_BACKEND env flag, then pallas-iff-TPU;
    ``graph`` follows ``backend``'s kind when not given).  Returns (final
    state, per-scan-step metrics stacked over the whole run, cluster-count
    after each stage-2).
    """
    if backend is None:
        backend = BackendConfig.create().interact(ops.n_users, d,
                                                  hyper.n_candidates)
    if graph is None:
        graph = BackendConfig(
            kind=backend.kind, precision=backend.precision,
        ).graph(ops.n_users, interpret=backend.interpret)
    return _run(ops, key, hyper, n_epochs, d, backend, graph)


@partial(jax.jit, static_argnames=("ops", "hyper", "n_epochs", "d", "backend",
                                   "graph"))
def _run(
    ops: EnvOps,
    key: jax.Array,
    hyper: BanditHyper,
    n_epochs: int,
    d: int,
    backend: InteractBackend,
    graph: GraphBackend,
) -> tuple[DistCLUBState, Metrics, jnp.ndarray]:
    with jax.named_scope("init"):
        state = init_state(ops.n_users, d, hyper)
        keys = jax.random.split(key, n_epochs)

    def epoch(state, k):
        k1, k3 = jax.random.split(k)
        state, m1 = stage1(state, ops, k1, hyper, backend)
        state = stage2(state, hyper, d, graph)
        with jax.named_scope("stage2"):
            n_clu = clustering.num_clusters(state.graph.labels)
        state, m3 = stage3(state, ops, k3, hyper, backend)
        state = stage4(state, hyper)
        with jax.named_scope("round_metrics"):
            metrics = jax.tree.map(
                lambda a, b: jnp.concatenate([a, b]), m1, m3
            )
        return state, (metrics, n_clu)

    # the epoch loop's own work (key split, loop bookkeeping, stacking the
    # metrics) is named ``epoch``; each stage inside it names itself
    with jax.named_scope("epoch"):
        state, (metrics, n_clusters) = jax.lax.scan(epoch, state, keys)
        metrics = jax.tree.map(lambda x: x.reshape(-1), metrics)
    with jax.named_scope("refresh_gram"):
        return refresh_gram(state), metrics, n_clusters
