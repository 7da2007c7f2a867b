"""User-graph maintenance: edge pruning + connected components + aggregates.

The paper's Stage-2 ("updateNetwork + recomputeClusters") maps onto three
fully data-parallel pieces:

  1. prune_edges     — drop edge (i,j) when |v_i - v_j| exceeds the CLUB
                       confidence-width threshold (Gentile et al. 2014):
                       cb(occ) = sqrt((1 + log(1+occ)) / (1 + occ)).
  2. connected_components — iterative min-label propagation (the JAX-native
                       equivalent of Spark/GraphX connectedComponents): each
                       hop takes the min label over neighbours; a
                       ``lax.while_loop`` runs to fixed point.  At most n
                       hops; in practice O(graph diameter).
  3. cluster_stats   — per-cluster Gram/bias aggregation via segment_sum
                       keyed by label (the treeReduce of the paper; in the
                       sharded runtime this becomes a local segment_sum
                       followed by a mesh psum — the ICI all-reduce tree).

Labels live in user-id space (label = smallest user id in the component), so
all shapes stay static regardless of how many clusters exist.

Representation split: the DistCLUB / CLUB drivers carry the adjacency
**bit-packed** (``[n, ceil(n/32)] uint32`` rounded up to the graph
kernels' blocks, see ``repro.kernels.graph.ops.stored_shape``) and
run stage 2 through the ``GraphBackend`` engine — pruning only ever clears
bits, so packing is lossless and AND-monotone, and it cuts graph memory 32x
(the dense graph cannot even be allocated at the ROADMAP's million-user
scale).  The *dense* ``prune_edges`` / ``connected_components`` below are
kept as the numerical oracle for tests and for DCCB, whose gossip protocol
does per-edge scatter updates on a small dense matrix.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..kernels.graph import ops as graph_ops
from ..kernels.spdinv.ops import spd_inverse
from .types import ClusterStats, GraphState


def dense_adj(n_users: int) -> jnp.ndarray:
    """[n, n] bool fully-connected adjacency minus self edges (oracle/DCCB)."""
    return jnp.ones((n_users, n_users), bool) & ~jnp.eye(n_users, dtype=bool)


def init_graph(n_users: int) -> GraphState:
    """Packed fully-connected graph at its stored shape: ``[n,
    ceil(n/32)]`` uint32 rows rounded up to the graph kernels' blocks,
    the padding all 0 (``repro.kernels.graph.ops``)."""
    adj = graph_ops.init_stored_adj(n_users)
    return GraphState(adj=adj, labels=jnp.zeros((n_users,), jnp.int32))


def cb_width(occ: jnp.ndarray, dtype=jnp.float32) -> jnp.ndarray:
    """CLUB's confidence-ball width around a user's estimate."""
    occf = occ.astype(dtype)
    return jnp.sqrt((1.0 + jnp.log1p(occf)) / (1.0 + occf))


def prune_edges(
    adj: jnp.ndarray,     # [n, n] bool
    v: jnp.ndarray,       # [n, d] current user vectors (Minv b)
    occ: jnp.ndarray,     # [n] i32
    gamma: float,
) -> jnp.ndarray:
    """Remove edges between users whose estimates diverged. Symmetric.

    Dense oracle: materializes the [n, n] distance matrix, so it is only
    used on small graphs (tests, DCCB).  Production paths go through
    ``GraphBackend.prune`` on the packed adjacency.
    """
    sq = jnp.sum(v * v, axis=-1)
    d2 = sq[:, None] + sq[None, :] - 2.0 * (v @ v.T)
    dist = jnp.sqrt(jnp.maximum(d2, 0.0))
    thresh = gamma * (cb_width(occ)[:, None] + cb_width(occ)[None, :])
    return adj & (dist < thresh)


def connected_components(adj: jnp.ndarray) -> jnp.ndarray:
    """Min-label propagation with pointer doubling.

    Each hop takes the min label over neighbours, then chases label->label
    links (``labels[labels]``) — the shortcutting step of classic
    pointer-jumping CC.  A label is always the id of some node in the same
    component with an equal-or-smaller id, so the jump preserves the
    min-label invariant while collapsing label chains geometrically: the
    ``while_loop`` converges in O(log n) hops instead of O(graph diameter).
    Returns [n] i32 labels (component min id).
    """
    n = adj.shape[0]
    init = jnp.arange(n, dtype=jnp.int32)
    big = jnp.int32(n)

    def hop(labels):
        # min over neighbours' labels (and own), then pointer-double
        neigh = jnp.where(adj, labels[None, :], big)
        l1 = jnp.minimum(labels, jnp.min(neigh, axis=1))
        return jnp.minimum(l1, l1[l1])

    def cond(carry):
        labels, changed, it = carry
        return changed & (it < n)

    def body(carry):
        labels, _, it = carry
        new = hop(labels)
        return new, jnp.any(new != labels), it + 1

    labels, _, _ = jax.lax.while_loop(cond, body, (init, jnp.array(True), 0))
    return labels


def cluster_stats(
    labels: jnp.ndarray,   # [n] i32
    M: jnp.ndarray,        # [n, d, d]
    b: jnp.ndarray,        # [n, d]
    d: int,
) -> ClusterStats:
    """Aggregate user statistics into label-indexed cluster statistics.

    Follows the paper: Mc = I + sum_u (Mu - I), bc = sum_u bu.  (Summing raw
    Mu would stack one identity per member; CLUB's estimator uses a single
    ridge term.)
    """
    n = labels.shape[0]
    eye = jnp.eye(d, dtype=M.dtype)
    Mc = jax.ops.segment_sum(M - eye, labels, num_segments=n) + eye
    bc = jax.ops.segment_sum(b, labels, num_segments=n)
    size = jax.ops.segment_sum(jnp.ones_like(labels), labels, num_segments=n)
    # one batched inverse per stage-2 (not per interaction).  Rows whose id
    # is not a live label are the identity, and so are their inverses.
    Mcinv = spd_inverse(Mc)
    return ClusterStats(
        Mc=Mc,
        Mcinv=Mcinv,
        bc=bc,
        size=size,
        seen=jnp.zeros((n,), jnp.int32),
    )


def num_clusters(labels: jnp.ndarray) -> jnp.ndarray:
    """Number of distinct labels = number of users that are their own label."""
    n = labels.shape[0]
    return jnp.sum(labels == jnp.arange(n, dtype=labels.dtype))
