"""Core datatypes for the CLUB-family bandit algorithms.

Everything is a flat NamedTuple of arrays so states are pytrees that move
through jit / scan / shard_map without ceremony.  The user axis (``n``) is
the distribution axis: in the sharded runtime every array whose leading dim
is ``n`` is sharded over the flattened device mesh.
"""
from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp


class BanditHyper(NamedTuple):
    """Hyper-parameters shared by CLUB / DCCB / DistCLUB (paper Table 2)."""

    alpha: float = 0.03        # UCB exploration coefficient
    beta: float = 2.0          # DistCLUB cluster-penalizing threshold
    gamma: float = 0.7         # edge-deletion threshold multiplier
    sigma: int = 16            # initial uRounds/cRounds split (paper: 2500)
    delta_net: int = 64        # CLUB network-update period (paper: 2000)
    buffer_size: int = 32      # DCCB buffer length (paper: 5000)
    n_candidates: int = 20     # |context set| presented per interaction
    max_rounds: int = 64       # static bound for uRounds/cRounds scan lengths


class LinUCBState(NamedTuple):
    """Per-user linear-bandit sufficient statistics.

    M    : [n, d, d]  Gram matrix  I + sum x x^T
    Minv : [n, d, d]  maintained inverse (Sherman-Morrison; exact)
    b    : [n, d]     reward-weighted context sum
    occ  : [n] i32    interaction counts
    """

    M: jnp.ndarray
    Minv: jnp.ndarray
    b: jnp.ndarray
    occ: jnp.ndarray


class GraphState(NamedTuple):
    """User-similarity graph + current clustering.

    adj      : [n, ceil(n/32)] uint32 — bit-packed rows, LSB-first (bit
               ``j % 32`` of word ``j // 32`` = edge (i, j); layout in
               ``repro.kernels.graph.ref``), stored with rows and words
               rounded up to the graph kernels' blocks, the padding 0
               (``repro.kernels.graph.ops.stored_shape``).  Row-sharded in
               the distributed runtime.  Edges are only ever pruned, so the
               packing is AND-monotone and 32x smaller than dense bool.
    labels   : [n] i32      cluster label = min user-id in the component
    """

    adj: jnp.ndarray
    labels: jnp.ndarray


class ClusterStats(NamedTuple):
    """Per-cluster aggregates, indexed by cluster label (a user id).

    Rows for ids that are not a current label are garbage and never read.
    """

    Mc: jnp.ndarray      # [n, d, d]
    Mcinv: jnp.ndarray   # [n, d, d]
    bc: jnp.ndarray      # [n, d]
    size: jnp.ndarray    # [n] i32   users per cluster
    seen: jnp.ndarray    # [n] i32   interactions since last stage-2


class DistCLUBState(NamedTuple):
    lin: LinUCBState
    graph: GraphState
    clusters: ClusterStats
    u_rounds: jnp.ndarray   # [n] i32 per-user stage-1 budget
    c_rounds: jnp.ndarray   # [n] i32 per-user stage-3 budget
    comm_bytes: jnp.ndarray  # [] f64-ish counter (f32) of bytes shipped


class Metrics(NamedTuple):
    """Streaming evaluation counters (one scalar slot per logical step)."""

    reward: jnp.ndarray      # realized reward (summed over the step's batch)
    regret: jnp.ndarray      # expected-best minus expected-chosen
    rand_reward: jnp.ndarray  # reward of a uniform-random policy (paper's RAN)
    interactions: jnp.ndarray  # number of (unmasked) interactions this step
