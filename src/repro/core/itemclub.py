"""Item-side CLUB clustering over the `Catalog` + tile-aligned UCB
bounds — the structure the cluster-pruned retrieval path serves from.

DistCLUB clusters USERS; `CatalogEnv` plants the mirrored structure on
the ITEM side (region centroids) that the streaming top-K engine never
exploited.  This module learns that structure online — CLUB-style, from
per-item reward statistics — and lays the catalog out so whole item
tiles can be skipped:

  1. `ItemStats` — per-slot serve counts + reward sums, folded
     duplicate-safely from served feedback (`observe_served`).  Items
     cluster on ``concat(normalize(emb), beta * rhat)``: embedding
     geometry plus the LEARNED mean reward, so two items of similar
     geometry but divergent realized reward separate (the CAB insight —
     the item side of the collaborative structure is learnable online).
  2. `build_clusters` — CLUB confidence pruning + connected components
     over a bounded ANCHOR set via the bit-packed adjacency + tiled
     edge-prune + fused CC-hop machinery of ``kernels/graph``
     (`GraphBackend`; a full graph over 2^18 items would need GiBs of
     adjacency — anchors keep stage-2-style cost while every item still
     gets a label by nearest-anchor assignment, chunked so the
     ``[capacity, A]`` distance matrix never materializes).  When
     ``capacity <= n_anchors`` every item IS an anchor and the
     clustering is the exact CLUB graph.
  3. Tile-aligned layout: a permutation ``perm`` (position -> slot id)
     sorts live slots by cluster label, dead slots last, and cached
     sorted copies of the serving bank plus per-tile summaries
     (centroid ``tile_mu``, radius ``tile_r``, max-norm ``tile_xn``,
     live count ``tile_n``) feed ``kernels.topk.ref.tile_bounds`` — a
     TRUE per-(user, tile) upper bound, so pruning is EXACT (shortlists
     bit-equal to unpruned; see ``kernels/topk/ref.py``).

Epoch contract (the churn-safety rule `serve` enforces): the cluster
state is stamped with the catalog epoch it was built from.  `publish`
is the only operation that mutates the serving bank and it always bumps
the epoch, so ``clusters.epoch == catalog.epoch`` iff the sorted copies
and tile tables still describe the serving truth — on mismatch the
pruned path FALLS BACK to unpruned scoring (never silently prunes with
stale bounds).  Rebuild lazily on the stage-2 cadence via
`refresh_clusters` (a no-op while the epoch still matches, unless
forced).

Sharding: the cluster tables are REPLICATED (`specs`).  Each item shard
takes its own position range of the sorted stream (`shard_slice`) —
because ``ids_sorted`` carries global slot ids and shortlist selection
is by (score, id) value, ANY partition of the position axis merges to
the identical shortlist, and the one-hot context assembly still
resolves slot ownership against the sharded bank.  ``capacity`` must be
divisible by ``tile_items * n_shards``.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from .backend import BackendConfig
from .catalog import dequantize


class ItemStats(NamedTuple):
    """Per-slot learned reward statistics (slot-indexed, like the
    catalog banks: a retired-then-reclaimed slot should be reset via
    :func:`reset_new_slots` after the publish that re-seats it)."""

    occ: jnp.ndarray    # [capacity] i32 times the slot's item was served
    rsum: jnp.ndarray   # [capacity] f32 summed realized reward


class ItemClusters(NamedTuple):
    """Epoch-stamped item-cluster state + the tile-aligned sorted layout
    the pruned retrieval kernels stream."""

    epoch: jnp.ndarray        # [] i32 catalog epoch the tables describe
    labels: jnp.ndarray       # [capacity] i32 cluster label per slot
    perm: jnp.ndarray         # [capacity] i32 position -> slot id
    emb_sorted: jnp.ndarray   # [capacity, d] serving bank emb[perm]
    #                             (bank dtype: f32/bf16/int8 codes)
    live_sorted: jnp.ndarray  # [capacity] f32 serving bank live[perm]
    scale_sorted: jnp.ndarray  # [capacity] f32 serving bank scale[perm]
    tile_mu: jnp.ndarray      # [T, d] live-item centroid per tile
    tile_r: jnp.ndarray       # [T] max live |x - mu| per tile
    tile_xn: jnp.ndarray      # [T] max live |x| per tile
    tile_n: jnp.ndarray       # [T] i32 live items per tile
    n_clusters: jnp.ndarray   # [] i32 distinct anchor labels

    @property
    def capacity(self) -> int:
        return self.perm.shape[0]

    @property
    def tile_items(self) -> int:
        return self.perm.shape[0] // self.tile_mu.shape[0]


class RetrievalMetrics(NamedTuple):
    """Per-transaction pruned-retrieval telemetry (replicated scalars;
    psum-combined across item shards)."""

    tiles_skipped: jnp.ndarray   # [] i32 tile visits skipped
    tiles_total: jnp.ndarray     # [] i32 tile visits possible
    pruned_active: jnp.ndarray   # [] i32 1 = pruned path ran, 0 = stale
    #                                 cluster table, fell back to unpruned
    fold_passes: jnp.ndarray     # [] i32 feedback-fold passes the
    #                                 transaction ran (its largest user
    #                                 multiplicity; 0 with no fold)

    def skip_ratio(self) -> float:
        """Host-side tiles_skipped / tiles_total (0 when fallen back)."""
        return float(self.tiles_skipped) / max(1.0, float(self.tiles_total))


# ---------------------------------------------------------------------------
# learned per-item reward statistics
# ---------------------------------------------------------------------------


def init_stats(capacity: int) -> ItemStats:
    return ItemStats(occ=jnp.zeros((capacity,), jnp.int32),
                     rsum=jnp.zeros((capacity,), jnp.float32))


@jax.jit
def observe_served(stats: ItemStats, item_ids: jnp.ndarray,
                   rewards: jnp.ndarray,
                   valid: jnp.ndarray | None = None) -> ItemStats:
    """Fold one served batch: ``item_ids [B]`` global slot ids (< 0 =
    padding), ``rewards [B]`` realized rewards.  Scatter-add, so
    duplicate items in one batch fold exactly like sequential serves."""
    cap = stats.occ.shape[0]
    ok = (item_ids >= 0) & (item_ids < cap)
    if valid is not None:
        ok = ok & valid
    tgt = jnp.where(ok, item_ids, cap)          # out-of-range writes drop
    return ItemStats(
        occ=stats.occ.at[tgt].add(ok.astype(jnp.int32), mode="drop"),
        rsum=stats.rsum.at[tgt].add(
            jnp.where(ok, rewards.astype(jnp.float32), 0.0), mode="drop"),
    )


@jax.jit
def reset_new_slots(stats: ItemStats, catalog) -> ItemStats:
    """Zero the statistics of slots whose resident item arrived at the
    CURRENT epoch (``born == epoch``) — call after a `publish` so a
    reclaimed slot never inherits its previous occupant's rewards."""
    bank = catalog.serving
    fresh = bank.born == catalog.epoch
    return ItemStats(occ=jnp.where(fresh, 0, stats.occ),
                     rsum=jnp.where(fresh, 0.0, stats.rsum))


# ---------------------------------------------------------------------------
# CLUB clustering over anchors + nearest-anchor assignment
# ---------------------------------------------------------------------------


def _item_features(emb: jnp.ndarray, stats: ItemStats,
                   beta: float) -> jnp.ndarray:
    """[capacity, d + 1] — unit-normalized embedding ++ beta * learned
    mean reward (rhat = rsum / (1 + occ), the ridge-style estimate that
    is 0 for never-served items)."""
    nrm = jnp.maximum(jnp.linalg.norm(emb, axis=-1, keepdims=True), 1e-9)
    rhat = stats.rsum / (1.0 + stats.occ.astype(jnp.float32))
    return jnp.concatenate([emb / nrm, beta * rhat[:, None]], axis=1)


def _nearest_anchor(z: jnp.ndarray, z_a: jnp.ndarray,
                    chunk: int = 4096) -> jnp.ndarray:
    """argmin_a |z_i - z_a| per row, chunked so the [capacity, A]
    distance matrix never materializes.  Ties break on the smaller
    anchor index (argmin), so when every item is its own anchor the
    assignment is exactly the identity."""
    cap = z.shape[0]
    cb = min(chunk, cap)
    pad = (-cap) % cb
    zp = jnp.pad(z, ((0, pad), (0, 0)))
    a2 = jnp.sum(z_a * z_a, axis=1)

    def blk(zb):
        d2 = (jnp.sum(zb * zb, axis=1)[:, None]
              - 2.0 * (zb @ z_a.T) + a2[None])
        return jnp.argmin(d2, axis=1).astype(jnp.int32)

    out = jax.lax.map(blk, zp.reshape((cap + pad) // cb, cb, -1))
    return out.reshape(cap + pad)[:cap]


def build_clusters(catalog, stats: ItemStats | None = None, *,
                   tile_items: int = 512, n_anchors: int = 512,
                   gamma: float = 0.5, beta: float = 1.0,
                   kind: str | None = None,
                   interpret: bool | None = None) -> ItemClusters:
    """Cluster the SERVING bank and lay it out tile-aligned.

    CLUB pruning runs on a bounded anchor set (the first ``n_anchors``
    live slots in id order; every slot when ``capacity <= n_anchors``)
    through the packed-adjacency `GraphBackend` — edge (i, j) survives
    iff ``|z_i - z_j| < gamma (cb(occ_i) + cb(occ_j))``, components are
    fused CC hops — then every slot takes its nearest anchor's label.
    Dead slots sort AFTER every label so they pool in trailing tiles
    (bound -inf, skipped as soon as any shortlist floor exists).

    ``capacity % tile_items == 0`` is required (and on an S-way item
    shard, ``capacity % (tile_items * S) == 0`` so each shard's position
    range is whole tiles).  The result is stamped with the catalog's
    CURRENT epoch; any later `publish` invalidates it (see module
    docstring)."""
    bank = catalog.serving
    cap = catalog.capacity
    if cap % tile_items:
        raise ValueError(f"capacity {cap} % tile_items {tile_items} != 0")
    if stats is None:
        stats = init_stats(cap)

    # features, tile summaries and bounds all run on the DEQUANTIZED
    # stream — the exact f32 values the pruned kernels score — so the
    # bounds dominate what is actually scored (f32 banks: identity)
    emb_f = dequantize(bank)
    z = _item_features(emb_f, stats, beta)
    # live slots first (stable -> ascending id), like add_items' slot scan
    by_live = jnp.argsort(-bank.live, stable=True).astype(jnp.int32)
    A = min(n_anchors, cap)
    anchor_ids = by_live[:A]
    z_a = z[anchor_ids]

    gb = BackendConfig.create(kind).graph(A, A, interpret=interpret)
    adj = gb.init_adj()
    adj = gb.prune(adj, z_a, stats.occ[anchor_ids], gamma)
    anchor_labels = gb.cc(adj)                 # [A] i32 in [0, A)

    labels = anchor_labels[_nearest_anchor(z, z_a)]
    n_clusters = jnp.sum(
        (jnp.bincount(anchor_labels, length=A) > 0).astype(jnp.int32))

    # dead slots get label A (past every anchor label) so a stable sort
    # pushes them into the trailing tiles
    sort_key = jnp.where(bank.live > 0, labels, A)
    perm = jnp.argsort(sort_key, stable=True).astype(jnp.int32)
    emb_sorted = bank.emb[perm]          # stored dtype — kernels dequant
    live_sorted = bank.live[perm]
    scale_sorted = bank.scale[perm]

    T = cap // tile_items
    d = bank.emb.shape[1]
    et = emb_f[perm].reshape(T, tile_items, -1)
    lt = live_sorted.reshape(T, tile_items)
    cnt = jnp.sum(lt, axis=1)
    mu = (jnp.sum(et * lt[..., None], axis=1)
          / jnp.maximum(cnt, 1.0)[:, None])
    dist = jnp.linalg.norm(et - mu[:, None, :], axis=-1)
    tile_r = jnp.max(jnp.where(lt > 0, dist, 0.0), axis=1)
    tile_xn = jnp.max(
        jnp.where(lt > 0, jnp.linalg.norm(et, axis=-1), 0.0), axis=1)
    # quantized banks: widen radius/max-norm by the per-tile quantization
    # error bound so the bounds stay conservative even against re-rounded
    # dequant chains (f32: widening is exactly zero — bit-identical)
    if bank.emb.dtype == jnp.int8:
        st = scale_sorted.reshape(T, tile_items)
        qeps = jnp.sqrt(float(d)) * 0.5 * jnp.max(
            jnp.where(lt > 0, st, 0.0), axis=1)
    elif bank.emb.dtype == jnp.bfloat16:
        qeps = tile_xn * 2.0 ** -8        # bf16 has 8 mantissa bits
    else:
        qeps = jnp.zeros_like(tile_xn)
    tile_r = tile_r + qeps
    tile_xn = tile_xn + qeps

    return ItemClusters(
        epoch=jnp.asarray(catalog.epoch, jnp.int32),
        labels=labels.astype(jnp.int32), perm=perm,
        emb_sorted=emb_sorted, live_sorted=live_sorted,
        scale_sorted=scale_sorted.astype(jnp.float32),
        tile_mu=mu.astype(jnp.float32), tile_r=tile_r.astype(jnp.float32),
        tile_xn=tile_xn.astype(jnp.float32), tile_n=cnt.astype(jnp.int32),
        n_clusters=n_clusters,
    )


def is_fresh(clusters: ItemClusters, catalog) -> bool:
    """Host-side: do the tables still describe the serving bank?"""
    return int(clusters.epoch) == int(catalog.epoch)


def refresh_clusters(clusters: ItemClusters, catalog,
                     stats: ItemStats | None = None, *,
                     force: bool = False, **build_kw) -> ItemClusters:
    """Lazy rebuild: a no-op while the epoch still matches (pass
    ``force=True`` on the stage-2 cadence to fold fresh reward
    statistics into the clustering even without churn).  Keyword args
    forward to :func:`build_clusters`."""
    if not force and is_fresh(clusters, catalog):
        return clusters
    build_kw.setdefault("tile_items", clusters.tile_items)
    return build_clusters(catalog, stats, **build_kw)


# ---------------------------------------------------------------------------
# sharding
# ---------------------------------------------------------------------------


def specs() -> ItemClusters:
    """PartitionSpecs: the cluster tables REPLICATE (each item shard
    slices its own position range via :func:`shard_slice`)."""
    return ItemClusters(epoch=P(), labels=P(), perm=P(), emb_sorted=P(),
                        live_sorted=P(), scale_sorted=P(), tile_mu=P(),
                        tile_r=P(), tile_xn=P(), tile_n=P(),
                        n_clusters=P())


def shard_slice(clusters: ItemClusters, shard, n_local: int):
    """This shard's piece of the sorted stream: positions
    ``[shard * n_local, ...)`` and their whole tiles.  Returns
    ``(emb, live, ids, scale, tile_mu, tile_r, tile_xn, tile_n)`` —
    ``ids`` are the GLOBAL slot ids, so per-shard shortlists merge
    bit-equal to the single-host stream (selection is by value)."""
    tile = clusters.tile_items
    if n_local % tile:
        raise ValueError(
            f"shard slice {n_local} % tile_items {tile} != 0 — build "
            "clusters with tile_items dividing capacity // n_shards")
    T_local = n_local // tile
    row0 = shard * n_local
    t0 = shard * T_local
    sl = jax.lax.dynamic_slice_in_dim
    return (sl(clusters.emb_sorted, row0, n_local),
            sl(clusters.live_sorted, row0, n_local),
            sl(clusters.perm, row0, n_local),
            sl(clusters.scale_sorted, row0, n_local),
            sl(clusters.tile_mu, t0, T_local),
            sl(clusters.tile_r, t0, T_local),
            sl(clusters.tile_xn, t0, T_local),
            sl(clusters.tile_n, t0, T_local))
