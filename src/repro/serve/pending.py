"""The pending-decision ring buffer: recommend now, fold feedback later.

Real traffic never returns a reward inside the transaction that issued
the recommendation — feedback arrives late, out of order, duplicated, or
not at all.  The :class:`PendingBuffer` is the device-resident state that
bridges the two halves: ``serve.recommend`` on a buffer-enabled session
issues choices AND enqueues one decision per valid request —
``(uid, choice, x digest, decision id, deadline)`` — into a
fixed-capacity ring; ``serve.observe_delayed`` folds whatever feedback
has arrived, matched by decision id, whenever it arrives.

Layout and semantics
  * slot ``decision_id % capacity`` holds the decision — decision ids are
    a monotone i32 counter, so a batch of ``B <= capacity`` consecutive
    ids always lands on distinct slots (the session enforces the width).
  * ``x`` is the CHOSEN context row the feedback fold needs — the exact
    psum-combined ``[d]`` vector the synchronous ``step`` would fold —
    so a delayed fold is bit-identical to the synchronous one.
  * the ``clock`` ticks once per issue transaction; a decision issued at
    clock ``c`` with TTL ``t`` carries ``deadline = c + t`` and is
    dropped (slot freed, ``expired`` counted) at the first issue whose
    clock exceeds the deadline — i.e. it survives exactly ``t``
    subsequent ``recommend`` transactions.
  * capacity backpressure: enqueuing onto a slot that still holds an
    unmatched, unexpired decision evicts it (``dropped`` counted) — the
    ring never blocks the serving path.
  * duplicate delivery: a matched slot is cleared, so a second delivery
    of the same decision id finds no resident decision and is counted
    ``unmatched`` — never folded twice.  Duplicates INSIDE one feedback
    batch fold only their first occurrence.
  * catalog churn: every decision records the catalog ``epoch`` it was
    issued at.  When the serving layer passes a staleness mask to
    :func:`match` (``serve.observe_delayed(..., catalog=...)``), matched
    feedback whose item churned since issue — retired, slot re-claimed,
    or more than ONE epoch behind the published catalog — is QUARANTINED:
    the slot is freed and the entry counted ``stale``, never folded.

Conservation identity (asserted by the churn fault suite): every issued
decision resolves exactly once —

    issued == matched + in_flight + expired + dropped + stale

Every array is replicated on a sharded session (:func:`specs`): the
enqueue consumes the psum-combined choice/context, so all shards hold
byte-identical buffers and the fold re-derives ownership per shard
exactly like the synchronous path.  All counters are lifetime totals.
"""
from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp

from jax.sharding import PartitionSpec as P


class PendingBuffer(NamedTuple):
    uid: jnp.ndarray        # [C] i32 user id of the decision (-1 = free)
    choice: jnp.ndarray     # [C] i32 chosen slate slot / global item id
    x: jnp.ndarray          # [C, d] f32 chosen context (the fold digest)
    decision: jnp.ndarray   # [C] i32 resident decision id (-1 = free)
    deadline: jnp.ndarray   # [C] i32 last clock at which feedback folds
    epoch: jnp.ndarray      # [C] i32 catalog epoch the decision issued at
    next_id: jnp.ndarray    # [] i32 monotone decision-id counter
    clock: jnp.ndarray      # [] i32 issue-transaction counter
    issued: jnp.ndarray     # [] i32 VALID decisions enqueued (no padding)
    expired: jnp.ndarray    # [] i32 decisions dropped on TTL
    dropped: jnp.ndarray    # [] i32 decisions evicted by backpressure
    matched: jnp.ndarray    # [] i32 feedback entries folded
    unmatched: jnp.ndarray  # [] i32 feedback with no resident decision
    stale: jnp.ndarray      # [] i32 feedback quarantined (item churned)

    @property
    def capacity(self) -> int:
        return self.uid.shape[0]


def init(capacity: int, d: int) -> PendingBuffer:
    if capacity <= 0:
        raise ValueError(f"pending capacity must be positive, got {capacity}")
    z = jnp.zeros((), jnp.int32)
    return PendingBuffer(
        uid=jnp.full((capacity,), -1, jnp.int32),
        choice=jnp.full((capacity,), -1, jnp.int32),
        x=jnp.zeros((capacity, d), jnp.float32),
        decision=jnp.full((capacity,), -1, jnp.int32),
        deadline=jnp.zeros((capacity,), jnp.int32),
        epoch=jnp.zeros((capacity,), jnp.int32),
        next_id=z, clock=z, issued=z, expired=z, dropped=z, matched=z,
        unmatched=z, stale=z,
    )


def specs() -> PendingBuffer:
    """Replicated PartitionSpecs — the buffer is identical on every
    shard (it only ever consumes psum-combined values)."""
    return PendingBuffer(*(P() for _ in PendingBuffer._fields))


def clear(p: PendingBuffer) -> PendingBuffer:
    """Free every slot but KEEP ``next_id``/``clock``/counters — used by
    guardrail rollback, where in-flight feedback issued before the
    rollback must stay unmatchable (a reset id counter would let stale
    feedback alias fresh decisions)."""
    return p._replace(
        uid=jnp.full_like(p.uid, -1),
        decision=jnp.full_like(p.decision, -1),
    )


def in_flight(p: PendingBuffer) -> jnp.ndarray:
    return jnp.sum((p.uid >= 0).astype(jnp.int32))


def issue(p: PendingBuffer, uids: jnp.ndarray, choices: jnp.ndarray,
          x: jnp.ndarray, valid: jnp.ndarray, ttl: int,
          epoch: jnp.ndarray | None = None
          ) -> tuple[PendingBuffer, jnp.ndarray]:
    """Tick the clock, expire overdue decisions, enqueue the batch.

    Returns ``(buffer, decision_ids [B] i32)`` — padding requests
    (``valid`` False) consume an id but are not enqueued and return -1.
    ``ttl`` is static (part of the session's compiled-transaction key);
    ``epoch`` is the catalog epoch the batch was issued at (scalar i32;
    None — the slate path — records 0).
    """
    B = uids.shape[0]
    C = p.uid.shape[0]
    if epoch is None:
        epoch = jnp.zeros((), jnp.int32)
    clock = p.clock + 1
    overdue = (p.uid >= 0) & (p.deadline < clock)
    p = p._replace(
        uid=jnp.where(overdue, -1, p.uid),
        decision=jnp.where(overdue, -1, p.decision),
        clock=clock,
        expired=p.expired + jnp.sum(overdue.astype(jnp.int32)),
    )
    ids = p.next_id + jnp.arange(B, dtype=jnp.int32)
    slot = jnp.mod(ids, C)
    evict = valid & (p.uid[slot] >= 0)
    tgt = jnp.where(valid, slot, C)                  # drop padding writes
    return p._replace(
        uid=p.uid.at[tgt].set(uids, mode="drop"),
        choice=p.choice.at[tgt].set(choices, mode="drop"),
        x=p.x.at[tgt].set(x, mode="drop"),
        decision=p.decision.at[tgt].set(ids, mode="drop"),
        deadline=p.deadline.at[tgt].set(clock + ttl, mode="drop"),
        epoch=p.epoch.at[tgt].set(epoch, mode="drop"),
        next_id=p.next_id + B,
        issued=p.issued + jnp.sum(valid.astype(jnp.int32)),
        dropped=p.dropped + jnp.sum(evict.astype(jnp.int32)),
    ), jnp.where(valid, ids, -1)


def match(p: PendingBuffer, ids: jnp.ndarray,
          stale: jnp.ndarray | None = None
          ) -> tuple[PendingBuffer, jnp.ndarray, jnp.ndarray]:
    """Match a feedback batch by decision id and free the matched slots.

    Returns ``(buffer, uids [B] i32, x [B, d])`` ready for the session's
    duplicate-safe fold — entries that matched nothing (lost to TTL,
    already folded, duplicated inside the batch, or id -1 padding) come
    back with uid -1, which the fold treats as padding.

    ``stale [B]`` bool (from the serving layer's per-decision epoch/live
    check) QUARANTINES: a matched-but-stale entry frees its slot and
    counts ``stale`` instead of ``matched``, and surfaces as uid -1 so
    the fold never sees churned-item feedback.
    """
    C = p.uid.shape[0]
    if stale is None:
        stale = jnp.zeros(ids.shape, bool)
    slot = jnp.mod(jnp.where(ids >= 0, ids, 0), C)
    resident = (ids >= 0) & (p.decision[slot] == ids)
    # in-batch dedup: only the FIRST occurrence of a decision id folds
    eq = (ids[:, None] == ids[None, :]) & (ids >= 0)[:, None]
    first = jnp.sum(jnp.tril(eq, k=-1), axis=1) == 0
    hit = resident & first
    fold = hit & ~stale
    quarantined = hit & stale
    uids = jnp.where(fold, p.uid[slot], -1)
    x = p.x[slot]
    tgt = jnp.where(hit, slot, C)         # stale slots free too
    p = p._replace(
        uid=p.uid.at[tgt].set(-1, mode="drop"),
        decision=p.decision.at[tgt].set(-1, mode="drop"),
        matched=p.matched + jnp.sum(fold.astype(jnp.int32)),
        stale=p.stale + jnp.sum(quarantined.astype(jnp.int32)),
        unmatched=p.unmatched
        + jnp.sum(((ids >= 0) & ~hit).astype(jnp.int32)),
    )
    return p, uids, x


def conservation_gap(p: PendingBuffer) -> int:
    """issued - (matched + in_flight + expired + dropped + stale); zero
    iff every issued decision is accounted for exactly once.  The churn
    fault suite asserts this after every delivery."""
    resolved = p.matched + in_flight(p) + p.expired + p.dropped + p.stale
    return int(p.issued - resolved)


def stats(p: PendingBuffer) -> dict[str, float]:
    """Host-side counter snapshot (guardrails read ``occupancy``).
    ``issued`` counts VALID enqueued decisions (padding consumes an id
    but is never enqueued), so the conservation identity
    ``issued == matched + in_flight + expired + dropped + stale`` holds
    exactly on every buffer."""
    cap = p.capacity
    flight = int(in_flight(p))
    return {
        "capacity": cap,
        "in_flight": flight,
        "occupancy": flight / cap,
        "clock": int(p.clock),
        "issued": int(p.issued),
        "matched": int(p.matched),
        "unmatched": int(p.unmatched),
        "expired": int(p.expired),
        "dropped": int(p.dropped),
        "stale": int(p.stale),
    }
