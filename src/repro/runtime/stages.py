"""The four DistCLUB stages (paper Listing 3), written exactly once.

Every function here operates on a LOCAL user slice ``[n_local, ...]`` and a
``Collectives`` implementation (``runtime.collectives``):

  stage 1  ``personalized_rounds``  — zero communication
  stage 2  ``stage2_refresh``       — THE communicating stage: all-gather
                                      for edge pruning, label hops for
                                      connected components, psum for the
                                      cluster aggregates (the treeReduce)
  stage 3  ``cluster_rounds``       — zero communication (stats frozen)
  stage 4  ``stage4_rebalance``     — zero communication

``repro.core.distclub`` runs these with ``NullCollectives`` (n_local = n,
row0 = 0) and ``repro.distributed.distclub_shard`` binds them to ``lax``
collectives inside ``shard_map``; the single-host/sharded parity test is
structural, not aspirational — there is one stage body to diverge from.

Shard-awareness of the environment: the stages call
``ops.contexts_fn(key, occ, row0)`` / ``ops.rewards_fn(key, occ, contexts,
choice, row0)`` where ``row0`` is the global id of the slice's first user.
Environments fold their PRNG **per global user id** (``repro.core.env_ops``)
so the draws for user ``u`` are identical no matter how the user axis is
sharded — metrics then agree across shardings up to fp contraction order
(psum vs flat sums in stage 2 and in the metric reductions).

Lazy-snapshot semantics (one source of truth): the per-user cluster
snapshots (Mcinv[label], bc[label], and the cluster mean-occ) are taken at
stage 2 and frozen for the whole epoch — stage 3's beta heuristic AND
stage 4's rebalancing both read the stage-2 snapshot.  The single-host
driver historically fed stage 4 a stage-3-updated ``seen`` counter while
the sharded driver used the stage-2 snapshot; unifying on the snapshot
(this module) fixed that divergence — see
``tests/test_algorithms.py::test_stage4_uses_stage2_snapshot``.

The interaction loop (``interaction_rounds``) is also the inner loop of
both DCCB drivers (buffered updates are just a different ``update_fn``),
so all four bandit runtimes share one round protocol:
env draw -> score -> fused choose -> env reward -> update -> metrics.

Named scopes: each stage opens a ``jax.named_scope`` (``stage1`` ..
``stage4``), each round step names its parts (``env_contexts``,
``score``, ``choose``, ``env_rewards``, ``fold``, ``round_metrics``) and
stage 2 names ``prune``, ``cc``, ``gram_inverse``, ``cluster_reduce`` and
``cluster_inverse``.  XLA keeps them in each instruction's ``op_name``
metadata, so a device profile charges every operation to a stage; they
change nothing else in the compiled program.
"""
from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..core import linucb
from ..core.types import Metrics
from ..kernels.spdinv.ops import spd_inverse

# ---------------------------------------------------------------------------
# the shared interaction loop (stage 1, stage 3, DCCB inner loop)
# ---------------------------------------------------------------------------


def _metrics_of(realized, expected, best, rand, mask):
    m = mask.astype(realized.dtype)
    return Metrics(
        reward=jnp.sum(realized * m),
        regret=jnp.sum((best - expected) * m),
        rand_reward=jnp.sum(rand * m),
        interactions=jnp.sum(mask.astype(jnp.int32)),
    )


def interaction_rounds(be, ops, hyper, key, carry0, *, row0, n_steps,
                       occ_of, score_fn, update_fn, budget=None):
    """``n_steps`` lockstep interaction rounds over a local user slice.

    One scan step = one (masked) interaction for every local user:

      contexts = ops.contexts_fn(k, occ, row0)         # env draw
      w, Minv  = score_fn(carry)                       # stage-specific
      x, choice = be.choose(w, Minv, contexts, occ, alpha)   # fused engine
      rewards  = ops.rewards_fn(k, occ, contexts, choice, row0)
      carry    = update_fn(carry, step_idx, x, realized, mask)

    ``carry0`` is an arbitrary pytree (pad it to the backend block shape
    ONCE before calling — only the fresh per-step context tensor is padded
    inside the loop).  ``occ_of(carry)`` returns the occupancy array at the
    carry's width; ``score_fn(carry) -> (w, minv_eff)`` at the same width.
    ``budget`` (un-padded ``[n_local] i32`` or None) masks users whose
    budget is exhausted; None = every user live every step (DCCB).
    ``update_fn`` receives ``realized`` and ``mask`` at logical/carry width
    respectively and owns any padding of its own inputs.

    Returns ``(carry, metrics)`` with per-step ``Metrics`` rows
    ``[n_steps]`` (local sums — psum them at the epoch boundary).
    """
    budget_p = None if budget is None else be.pad_users(budget)

    def step(carry, inp):
        step_idx, k = inp
        k_ctx, k_rew = jax.random.split(k)
        occ = occ_of(carry)
        occ_log = be.unpad_users(occ)
        mask = (jnp.ones(occ.shape, bool) if budget_p is None
                else step_idx < budget_p)
        with jax.named_scope("env_contexts"):
            contexts = ops.contexts_fn(k_ctx, occ_log, row0)
        with jax.named_scope("score"):
            w, minv_eff = score_fn(carry)
        with jax.named_scope("choose"):
            x, choice = be.choose(w, minv_eff, contexts, occ, hyper.alpha)
        with jax.named_scope("env_rewards"):
            realized, expected, best, rand = ops.rewards_fn(
                k_rew, occ_log, contexts, be.unpad_users(choice), row0
            )
        with jax.named_scope("fold"):
            carry = update_fn(carry, step_idx, x, realized, mask)
        with jax.named_scope("round_metrics"):
            metrics = _metrics_of(
                realized, expected, best, rand, be.unpad_users(mask)
            )
        return carry, metrics

    steps = jnp.arange(n_steps)
    keys = jax.random.split(key, n_steps)
    return jax.lax.scan(step, carry0, (steps, keys))


def _linucb_update(be):
    """The DistCLUB per-round update: M-free fused Sherman-Morrison."""

    def update(carry, step_idx, x, realized, mask):
        del step_idx
        Minv, b, occ = carry
        Minv, b = be.update_inv(Minv, b, x, be.pad_users(realized), mask)
        return (Minv, b, occ + mask.astype(jnp.int32))

    return update


def _bandit_rounds(be, ops, hyper, Minv, b, occ, budget, key, row0, score_fn):
    carry0 = (be.pad_gram(Minv), be.pad_vec(b), be.pad_users(occ))
    (Minv, b, occ), metrics = interaction_rounds(
        be, ops, hyper, key, carry0, row0=row0, n_steps=hyper.max_rounds,
        occ_of=lambda c: c[2], score_fn=score_fn,
        update_fn=_linucb_update(be), budget=budget,
    )
    return (be.unpad_gram(Minv), be.unpad_vec(b), be.unpad_users(occ),
            metrics)


def personalized_rounds(be, ops, hyper, Minv, b, occ, budget, key, row0):
    """Stage 1: user-based LinUCB rounds — embarrassingly parallel, the
    state is padded once per stage and the scan carries the padded state."""

    def score_own(carry):
        Minv_, b_, _ = carry
        return linucb.user_vector(Minv_, b_), Minv_

    with jax.named_scope("stage1"):
        return _bandit_rounds(be, ops, hyper, Minv, b, occ, budget, key,
                              row0, score_own)


def beta_gate(hyper, occ, umean_occ):
    """The paper's beta personalization heuristic: a user whose lifetime
    occupancy has reached ``beta`` times the cluster's mean occupancy
    scores with their OWN statistics instead of the cluster's.  Single
    definition shared by stage 3 and the serving layer's clustered
    policies."""
    return occ.astype(jnp.float32) >= hyper.beta * umean_occ


def mix_scores(use_own, v_own, v_clu, Minv_own, Minv_clu):
    """Per-user blend of personalized vs cluster scoring statistics:
    ``(w, minv_eff)`` for the fused choose.  Shared by stage 3 and the
    serving policies (``repro.serve``)."""
    w = jnp.where(use_own[:, None], v_own, v_clu)
    minv_eff = jnp.where(use_own[:, None, None], Minv_own, Minv_clu)
    return w, minv_eff


def cluster_rounds(be, ops, hyper, Minv, b, occ, budget, key, row0,
                   uMcinv, ubc, umean_occ):
    """Stage 3: cluster-based rounds with the beta personalization
    heuristic.  The per-user cluster snapshots (``uMcinv``/``ubc``/
    ``umean_occ``, from :func:`stage2_refresh`) are FROZEN for the whole
    stage (the paper's lazy semantics): they are padded and the cluster
    user-vector computed once, outside the scan."""
    def score_cluster(carry):
        Minv_, b_, occ_ = carry
        use_own = beta_gate(hyper, occ_, umean_p)
        v_own = linucb.user_vector(Minv_, b_)
        return mix_scores(use_own, v_own, v_clu, Minv_, uMcinv_p)

    with jax.named_scope("stage3"):
        uMcinv_p = be.pad_gram(uMcinv)
        ubc_p = be.pad_vec(ubc)
        v_clu = linucb.user_vector(uMcinv_p, ubc_p)
        umean_p = be.pad_users(umean_occ)
        return _bandit_rounds(be, ops, hyper, Minv, b, occ, budget, key,
                              row0, score_cluster)


# ---------------------------------------------------------------------------
# stage 2: the communication stage
# ---------------------------------------------------------------------------


def stage2_comm_bytes(n: int, d: int) -> int:
    """Modeled network bytes of one stage-2 refresh (paper Fig. 3, updated
    for the packed graph engine).  Single source of truth for both
    drivers and the tests.

    Per refresh: each user ships (M, b) once into the tree reduction and
    the cluster stats return along the same tree (``2 n (d^2 + d)`` f32
    words); edge pruning all-gathers the user vectors and counts
    (``n (d + 1)`` words); and each pointer-doubling CC hop exchanges the
    n i32 labels — ``ceil(log2 n) + 1`` hops bound the doubling schedule.
    The adjacency itself NEVER crosses the network: it is row-sharded and
    bit-packed, n^2/8 bytes of node-local HBM (32x below the dense bool
    graph).
    """
    hops = max(1, math.ceil(math.log2(max(n, 2))) + 1)
    return 4 * (2 * n * (d * d + d) + n * (d + 1) + hops * n)


def snapshot_mean_occ(seen, size, labels):
    """Cluster mean lifetime-occupancy snapshot, per user: stage 3's beta
    heuristic AND stage 4's rebalancing both read this stage-2 value."""
    return seen[labels].astype(jnp.float32) / jnp.maximum(size[labels], 1)


def connected_components(col, gb, adj, n, row0, n_local):
    """Min-label propagation over the packed local adjacency rows, with
    pointer doubling on the (replicated) labels.

    One hop = fused neighbour-min over each shard's packed rows
    (``gb.cc_hop``, n_local*n/8 bytes of HBM), a tiled all-gather of the
    fresh local labels (the stage's only traffic), then the comm-free
    shortcutting step ``min(l, l[l])`` that makes convergence O(log n)
    hops instead of O(diameter).  With null collectives this is exactly
    the single-host ``GraphBackend.cc`` hop sequence.
    """
    init = jnp.arange(n, dtype=jnp.int32)

    def cond(carry):
        _, changed, it = carry
        return changed & (it < n)

    def body(carry):
        labels, _, it = carry
        local = jax.lax.dynamic_slice_in_dim(labels, row0, n_local)
        new = col.all_gather(gb.cc_hop(adj, local, labels))
        new = jnp.minimum(new, new[new])
        return new, jnp.any(new != labels), it + 1

    labels, _, _ = jax.lax.while_loop(cond, body, (init, jnp.array(True), 0))
    return labels


class Stage2Refresh(NamedTuple):
    """Everything stage 2 produces, local-slice and replicated views both.

    The replicated tables (``Mc``/``bc``/``size``/``seen``, label-indexed,
    rows for non-label ids are garbage/identity and never read) exist so
    the single-host driver can expose its ``ClusterStats`` record (serving
    layer, checkpoints); the sharded epoch keeps only the per-user sharded
    snapshots and lets the tables die as transients — they dominated
    per-device HBM when carried (§Perf iteration 2).
    """

    adj: jnp.ndarray          # [n_local, words]  pruned packed rows
    labels: jnp.ndarray       # [n]               replicated
    Mc: jnp.ndarray           # [n, d, d]         replicated (transient)
    bc: jnp.ndarray           # [n, d]            replicated (transient)
    size: jnp.ndarray         # [n] i32           replicated (transient)
    seen: jnp.ndarray         # [n] i32           replicated (transient)
    uMcinv: jnp.ndarray       # [n_local, d, d]   per-user cluster snapshot
    ubc: jnp.ndarray          # [n_local, d]
    umean_occ: jnp.ndarray    # [n_local] f32     mean-occ snapshot
    n_clusters: jnp.ndarray   # [] i32
    comm_bytes: jnp.ndarray   # [] f32            modeled bytes this refresh


def stage2_refresh(col, gb, hyper, d, Minv, b, occ, adj) -> Stage2Refresh:
    """Network update + clustering + cluster statistics (the comm stage).

    The Gram matrix is NOT an input: ``M = inv(Minv)`` is recovered
    locally once per refresh (both runtimes carry only the inverse —
    dropping M cut the per-round state traffic by ~1/3).  The cluster
    aggregation is a local ``segment_sum`` followed by ``col.psum`` — the
    paper's treeReduce on the all-reduce tree.  ``seen`` is seeded so
    ``seen/size`` equals the cluster's mean lifetime occupancy (paper:
    "average interactions for users in the cluster") and is FROZEN until
    the next refresh.
    """
    n = gb.n_cols
    n_local = Minv.shape[0]
    row0 = col.axis_index() * n_local

    with jax.named_scope("stage2"):
        # serving sessions may carry Minv in a reduced Precision state
        # dtype; the solves/inversions here run in f32 (no-op upcast for
        # f32 state)
        Minv = Minv.astype(jnp.float32)
        with jax.named_scope("prune"):
            v_local = linucb.user_vector(Minv, b)             # [n_local, d]
            v_all = col.all_gather(v_local)                   # [n, d]
            occ_all = col.all_gather(occ)                     # [n]
            adj = gb.prune_rows(adj, v_local, occ, v_all, occ_all,
                                hyper.gamma)
        with jax.named_scope("cc"):
            labels = connected_components(col, gb, adj, n, row0, n_local)
            local_labels = jax.lax.dynamic_slice_in_dim(labels, row0,
                                                        n_local)

        eye = jnp.eye(d, dtype=jnp.float32)
        with jax.named_scope("gram_inverse"):
            M = spd_inverse(Minv)
        with jax.named_scope("cluster_reduce"):
            Mc = col.psum(
                jax.ops.segment_sum(M - eye, local_labels, num_segments=n)
            ) + eye
            bc = col.psum(jax.ops.segment_sum(b, local_labels,
                                              num_segments=n))
            size = col.psum(jax.ops.segment_sum(
                jnp.ones_like(local_labels), local_labels, num_segments=n))
            seen = col.psum(jax.ops.segment_sum(occ, local_labels,
                                                num_segments=n))

        with jax.named_scope("cluster_inverse"):
            uMcinv = spd_inverse(Mc[local_labels])            # [n_local,d,d]
        ubc = bc[local_labels]
        umean_occ = snapshot_mean_occ(seen, size, local_labels)
        n_clusters = jnp.sum(labels == jnp.arange(n, dtype=labels.dtype))
    return Stage2Refresh(
        adj=adj, labels=labels, Mc=Mc, bc=bc, size=size, seen=seen,
        uMcinv=uMcinv, ubc=ubc, umean_occ=umean_occ, n_clusters=n_clusters,
        comm_bytes=jnp.float32(stage2_comm_bytes(n, d)),
    )


# ---------------------------------------------------------------------------
# stage 4
# ---------------------------------------------------------------------------


def stage4_rebalance(hyper, occ, umean_occ, u_rounds, c_rounds):
    """Rebalance per-user budgets between personalized / cluster rounds.

    ``umean_occ`` is the STAGE-2 SNAPSHOT of the cluster mean occupancy
    (``Stage2Refresh.umean_occ``) — the same frozen value stage 3's beta
    heuristic reads.  Both runtimes use this definition; the single-host
    driver previously fed a stage-3-updated counter here (the fixed
    divergence).

    Invariant (load-bearing for ``data.datasets.epochs_for``): the shift
    ``delta`` conserves the per-user budget SUM ``u + c`` — but only until
    a clip engages.  Each budget is clipped to ``[0, max_rounds]`` (the
    static scan length), so a user can momentarily process fewer than
    ``u + c`` interactions per epoch; per-epoch interaction counts are
    therefore bounded by ``n * 2 * min(sigma, max_rounds)``, not fixed at
    ``n * 2 * sigma``.
    """
    with jax.named_scope("stage4"):
        delta = ((occ.astype(jnp.float32) - umean_occ) / 2.0
                 ).astype(jnp.int32)
        u_rounds = jnp.clip(u_rounds + delta, 0, hyper.max_rounds)
        c_rounds = jnp.clip(c_rounds - delta, 0, hyper.max_rounds)
    return u_rounds, c_rounds
