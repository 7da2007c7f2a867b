"""The persistent item catalog the retrieval engine serves against —
epoch-numbered and DOUBLE-BUFFERED for live churn.

A :class:`Catalog` holds TWO device-resident slot banks of item
embeddings plus liveness masks.  Exactly one bank — ``active`` — is the
serving truth; the other is the SHADOW staging area.  Mutators
(:func:`add_items` / :func:`retire_items`) stage into the shadow bank
only, so a flash crowd of arrivals or a mass retirement never perturbs
the bank in-flight transactions read; :func:`publish` then flips
``active`` and bumps ``epoch`` in ONE functional op — the whole swap is
a single atomic device update, with no host-side interleaving against
``serve.step_catalog``.

Slots, not items, are the unit of storage — retiring an item clears its
``live`` bit in the shadow bank (after publish the retrieval kernels
score it -inf), adding an item claims the lowest dead shadow slot — so
the array shapes (and therefore every compiled transaction touching the
catalog) are stable across churn.

Epoch accounting (the staleness contract ``serve.pending`` enforces):

  * ``epoch`` counts publishes.  Every pending decision records the
    epoch it was issued at.
  * ``born[bank, slot]`` is the epoch at which the slot's CURRENT
    resident item became servable — staged adds are stamped
    ``epoch + 1`` (the epoch their publish will create), so a slot that
    was retired and re-claimed by a different item is distinguishable
    from the item a stale decision chose.
  * in-flight decisions tolerate EXACTLY ONE stale epoch: feedback for a
    decision issued at epoch ``e`` folds while the published epoch is at
    most ``e + 1`` and its item is still live with ``born <= e``;
    anything older is quarantined (counted ``stale``, never folded).

Sharding: the catalog shards over the mesh on the ITEM axis (axis 1 of
the banked arrays; ``specs``/``distributed.distclub_shard
.named_shardings``).  Inside ``shard_map`` each device holds slot rows
``[axis_index * n_local, ...)`` of BOTH banks and shortlists only those
— the serving layer merges per-shard shortlists, so cross-device traffic
is ``O(B * K_short * shards)`` words instead of ``O(B * N_items)``.
``active``/``epoch`` are replicated scalars: the flip is atomic on every
shard at once.

Precision (`core.backend.Precision`): banks may store embeddings in bf16
or int8 instead of f32 — ``emb`` simply carries that dtype and a per-slot
f32 dequant ``scale`` rides along (1.0 except under int8, where
``dequantized = emb.astype(f32) * scale[:, None]``).  The initial
:func:`make_catalog` quantization shares one scale per ``scale_block``
contiguous slots (the tile granularity the retrieval kernels stream);
churn-added items get per-row scales — the group structure is a property
of the initial layout only, and every mutator/publish treats ``scale``
exactly like the other slot arrays, so scales survive double-buffered
publishes and slot reclaim bit-exactly.

Pure-functional like everything else: mutators return a new Catalog.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from .backend import Precision, resolve_precision

from jax.sharding import PartitionSpec as P


class Bank(NamedTuple):
    """One bank's view — what the retrieval kernels actually consume."""

    emb: jnp.ndarray    # [capacity, d] embeddings (f32/bf16/int8 codes;
                        #   dead slots: zeros)
    live: jnp.ndarray   # [capacity] f32 liveness (1 = servable)
    born: jnp.ndarray   # [capacity] i32 epoch the resident item arrived
    scale: jnp.ndarray  # [capacity] f32 int8 dequant scale (1.0 otherwise)


class Catalog(NamedTuple):
    emb: jnp.ndarray    # [2, capacity, d] per-bank embeddings (bank dtype)
    live: jnp.ndarray   # [2, capacity] f32 per-bank liveness
    born: jnp.ndarray   # [2, capacity] i32 per-bank arrival epoch
    scale: jnp.ndarray  # [2, capacity] f32 per-bank dequant scales
    active: jnp.ndarray  # [] i32 which bank serves (0/1)
    epoch: jnp.ndarray   # [] i32 publish counter

    @property
    def capacity(self) -> int:
        return self.live.shape[1]

    @property
    def d(self) -> int:
        return self.emb.shape[2]

    @property
    def serving(self) -> Bank:
        """The active bank — the only state serving transactions read."""
        return Bank(emb=self.emb[self.active], live=self.live[self.active],
                    born=self.born[self.active],
                    scale=self.scale[self.active])

    @property
    def staged(self) -> Bank:
        """The shadow bank — where add/retire churn accumulates until
        the next :func:`publish`."""
        shadow = 1 - self.active
        return Bank(emb=self.emb[shadow], live=self.live[shadow],
                    born=self.born[shadow], scale=self.scale[shadow])

    def n_live(self) -> jnp.ndarray:
        """Servable item count of the ACTIVE bank (staged churn does not
        move this until it publishes)."""
        return jnp.sum(self.live[self.active]).astype(jnp.int32)


def _quantize_rows(emb: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    """f32 rows -> int8 codes under per-row ``scale`` (maxabs/127)."""
    q = jnp.round(jnp.clip(emb / scale[:, None], -127.0, 127.0))
    return q.astype(jnp.int8)


def dequantize(bank: Bank) -> jnp.ndarray:
    """The f32 embedding view the scoring math runs on.  For f32 banks
    this is the identity (bit-exact); bf16 upcasts; int8 applies the
    per-slot scale.  The dtype branch is trace-time."""
    e = bank.emb.astype(jnp.float32)
    if bank.emb.dtype == jnp.int8:
        e = e * bank.scale[:, None]
    return e


def make_catalog(emb: jnp.ndarray, capacity: int | None = None, *,
                 precision: Precision | str | None = None) -> Catalog:
    """Catalog over ``emb [N, d]`` (all live, born at epoch 0), with
    ``capacity - N`` spare dead slots for future ``add_items``.  Both
    banks start identical, active bank 0, epoch 0.

    ``precision`` picks the bank storage dtype (``catalog_dtype``); int8
    quantizes with one shared scale per ``scale_block`` contiguous slots
    (per-block maxabs/127, floored at 1e-8 so all-dead blocks stay
    finite).  Default (None) resolves ``REPRO_PRECISION`` -> f32."""
    prec = resolve_precision(precision)
    N, d = emb.shape
    capacity = N if capacity is None else capacity
    if capacity < N:
        raise ValueError(f"capacity {capacity} < {N} items")
    full32 = jnp.zeros((capacity, d), jnp.float32).at[:N].set(emb)
    dt = prec.jnp_catalog
    if dt == jnp.int8:
        sb = min(prec.scale_block, capacity)
        gid = jnp.arange(capacity, dtype=jnp.int32) // sb
        ngroups = (capacity + sb - 1) // sb
        rowmax = jnp.max(jnp.abs(full32), axis=1)
        gmax = jnp.zeros((ngroups,), jnp.float32).at[gid].max(rowmax)
        scale = jnp.maximum(gmax, 1e-8)[gid] / 127.0
        full = _quantize_rows(full32, scale)
    else:
        full = full32.astype(dt)
        scale = jnp.ones((capacity,), jnp.float32)
    live = jnp.zeros((capacity,), jnp.float32).at[:N].set(1.0)
    z = jnp.zeros((), jnp.int32)
    return Catalog(
        emb=jnp.stack([full, full]),
        live=jnp.stack([live, live]),
        born=jnp.zeros((2, capacity), jnp.int32),
        scale=jnp.stack([scale, scale]),
        active=z, epoch=z,
    )


def random_catalog(key: jax.Array, n_items: int, d: int,
                   capacity: int | None = None, *,
                   precision: Precision | str | None = None) -> Catalog:
    """Unit-norm random embeddings — benchmark/test construction."""
    e = jax.random.normal(key, (n_items, d))
    e = e / jnp.linalg.norm(e, axis=-1, keepdims=True)
    return make_catalog(e, capacity=capacity, precision=precision)


def _write_bank(cat: Catalog, bank, emb, live, born, scale) -> Catalog:
    return cat._replace(
        emb=cat.emb.at[bank].set(emb),
        live=cat.live.at[bank].set(live),
        born=cat.born.at[bank].set(born),
        scale=cat.scale.at[bank].set(scale),
    )


@jax.jit
def retire_items(cat: Catalog, ids: jnp.ndarray
                 ) -> tuple[Catalog, jnp.ndarray]:
    """STAGE the retirement of ``ids`` into the shadow bank; returns
    ``(catalog, n_retired)`` where ``n_retired`` counts shadow slots
    that actually went live -> dead.  Serving is untouched until
    :func:`publish`.  Negative ids (ragged-batch padding), out-of-range
    ids, duplicates, and already-dead slots are all well-defined no-ops
    — they simply don't count."""
    shadow = 1 - cat.active
    live_s = cat.live[shadow]
    tgt = jnp.where(ids >= 0, ids, cat.capacity)
    new_live = live_s.at[tgt].set(0.0, mode="drop")
    n_retired = jnp.sum(live_s - new_live).astype(jnp.int32)
    return cat._replace(live=cat.live.at[shadow].set(new_live)), n_retired


@jax.jit
def add_items(cat: Catalog, emb_new: jnp.ndarray
              ) -> tuple[Catalog, jnp.ndarray, jnp.ndarray]:
    """STAGE ``emb_new [m, d]`` into the lowest dead SHADOW slots;
    returns ``(catalog, slot_ids [m], n_added)``.  The staged items are
    stamped ``born = epoch + 1`` — the epoch the next :func:`publish`
    creates — and serve only from that publish on.

    A PARTIAL FILL when fewer than ``m`` shadow slots are free: the
    first ``n_added`` rows (in input order) claim the dead slots in
    ascending id order, the overflow is NOT placed and gets slot id -1 —
    live items are never silently overwritten.  Callers that must make
    room stage retirements first (same shadow bank, so a
    retire-then-add lands on the freed slots) and re-add the remainder.
    """
    m = emb_new.shape[0]
    shadow = 1 - cat.active
    emb_s, live_s, born_s, scale_s = (cat.emb[shadow], cat.live[shadow],
                                      cat.born[shadow], cat.scale[shadow])
    # stable ascending sort of the 0/1 mask: dead slots first, id order
    order = jnp.argsort(live_s, stable=True).astype(jnp.int32)
    n_free = (cat.capacity - jnp.sum(live_s)).astype(jnp.int32)
    placed = jnp.arange(m, dtype=jnp.int32) < n_free
    slot = order[jnp.minimum(jnp.arange(m), cat.capacity - 1)]
    tgt = jnp.where(placed, slot, cat.capacity)   # overflow writes drop
    emb32 = emb_new.astype(jnp.float32)
    if emb_s.dtype == jnp.int8:
        # churn-added items get per-row scales: the scale_block group
        # structure is a property of the initial layout only
        sc = jnp.maximum(jnp.max(jnp.abs(emb32), axis=1), 1e-8) / 127.0
        codes = _quantize_rows(emb32, sc)
    else:
        sc = jnp.ones((m,), jnp.float32)
        codes = emb32.astype(emb_s.dtype)
    cat = _write_bank(
        cat, shadow,
        emb_s.at[tgt].set(codes, mode="drop"),
        live_s.at[tgt].set(1.0, mode="drop"),
        born_s.at[tgt].set(cat.epoch + 1, mode="drop"),
        scale_s.at[tgt].set(sc, mode="drop"),
    )
    return cat, jnp.where(placed, slot, -1), jnp.sum(placed.astype(jnp.int32))


@jax.jit
def staged_churn(cat: Catalog) -> jnp.ndarray:
    """Number of slots whose staged state differs from the serving state
    — what the next :func:`publish` will change.  Feeds the guardrail
    churn-rate monitor."""
    a, s = cat.active, 1 - cat.active
    diff = ((cat.live[a] != cat.live[s])
            | (cat.born[a] != cat.born[s])
            | (cat.scale[a] != cat.scale[s])
            | jnp.any(cat.emb[a] != cat.emb[s], axis=-1))
    return jnp.sum(diff.astype(jnp.int32))


@jax.jit
def publish(cat: Catalog) -> Catalog:
    """Atomically flip the staged bank live: the shadow becomes the
    serving bank, ``epoch`` bumps by one, and the retiring bank is
    re-seeded as a copy of the newly published state (so the next round
    of staging starts from what is being served).  One functional op —
    under jit the swap is a single device update, never a torn
    host-side interleave."""
    new_active = 1 - cat.active
    emb_p, live_p, born_p, scale_p = (
        cat.emb[new_active], cat.live[new_active],
        cat.born[new_active], cat.scale[new_active])
    cat = _write_bank(cat, cat.active, emb_p, live_p, born_p, scale_p)
    return cat._replace(active=new_active, epoch=cat.epoch + 1)


@jax.jit
def torn_publish(cat: Catalog, keep_mask: jnp.ndarray) -> Catalog:
    """FAULT INJECTION ONLY — a publish where only ``keep_mask
    [capacity]`` slots' staged changes land (the rest flip back to their
    pre-churn state) before the atomic swap.  Models the torn/partial
    swap a non-double-buffered implementation risks; the epoch still
    bumps, so quarantine accounting stays well-defined while serving
    quality degrades.  Used by ``serve.faults`` and the churn tests."""
    shadow = 1 - cat.active
    keep = keep_mask.astype(bool)
    cat = _write_bank(
        cat, shadow,
        jnp.where(keep[:, None], cat.emb[shadow], cat.emb[cat.active]),
        jnp.where(keep, cat.live[shadow], cat.live[cat.active]),
        jnp.where(keep, cat.born[shadow], cat.born[cat.active]),
        jnp.where(keep, cat.scale[shadow], cat.scale[cat.active]),
    )
    return publish(cat)


def specs(axes) -> Catalog:
    """PartitionSpecs for an item-axis sharding over mesh ``axes`` —
    banks shard on their SLOT axis, the bank/flip scalars replicate."""
    return Catalog(emb=P(None, axes), live=P(None, axes),
                   born=P(None, axes), scale=P(None, axes),
                   active=P(), epoch=P())
