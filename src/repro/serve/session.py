"""`OnlineBandit`: policy-pluggable online serving sessions on the stage
engine.

The hot path is ONE jit-compiled transaction per request batch —

    session, choices, metrics = serve.step(
        session, key, user_ids, contexts, reward_fn)

— score (policy-mixed statistics), fused choose (`InteractBackend`, the
`[B, K]` score tensor never hits HBM on the pallas engine), reward,
duplicate-safe feedback fold, and a trace-friendly refresh (`lax.cond` on
the interaction budget; the old host-synced `int(...)` check is gone).
For real request/feedback splits the transaction decomposes into the two
halves `recommend` (pure, no state change) and `observe` (feedback fold +
refresh schedule).

Fault-tolerant feedback (README "Fault tolerance & guardrails"): a
session created with ``pending_capacity > 0`` carries a persistent
device-resident ring of in-flight decisions (`serve.pending`).  On such
a session `recommend`/`recommend_catalog` ISSUE: they return
``(session, choices, decision_ids)`` (catalog:
``(session, item_ids, decision_ids, slots, ctx)``), enqueuing one
decision per valid request, and `observe_delayed(session, decision_ids,
rewards)` folds feedback matched by decision id whenever it arrives —
exact under out-of-order, duplicated, and lossy delivery, dropping on
TTL with counted `expired`, all inside the jit transaction.  With zero
delay the pair is bit-identical to the synchronous `step` (the buffer
stores the exact psum-combined chosen context the fold needs), on
single-host and sharded sessions alike (the buffer is replicated).
Under live catalog churn (README "Live catalog churn") every catalog
decision records its issue epoch, and `observe_delayed(...,
catalog=current_catalog)` quarantines feedback whose item churned since
issue — counted `stale`, extending the conservation identity to
issued == matched + in_flight + expired + dropped + stale.

Duplicate-user batches are EXACT.  A batch is decomposed by occurrence
rank (item i's rank = how many earlier items carry the same user id) and
folded rank-by-rank with `lax.fori_loop`: within one pass every live row
is a distinct user, so a single fused masked rank-1 sweep per pass equals
the sequential per-interaction fold.  Distinct-user batches take exactly
one pass — the common fast path costs one fused update, and matches the
offline `runtime.stages.interaction_rounds` update bit for bit.

Catalog-scale retrieval: `step_catalog`/`recommend_catalog` serve the
same transaction against a persistent `core.catalog.Catalog` instead of
a caller-supplied slate — the streaming top-K engine
(`core.backend.RetrievalBackend`, `kernels/topk`) shortlists each user's
`k_short` highest-UCB live items (per item shard on a sharded session,
merged by (score desc, id asc) — bit-equal to a single-host shortlist)
and the fused choose ranks the shortlist.  The `[B, N_items]` score
matrix never exists; comm on a sharded session is O(B k_short shards).

Sharding: `OnlineBandit.sharded(mesh, ...)` binds the SAME step body to
`LaxCollectives` under `shard_map` — per-user state rows are sharded over
the mesh, the request batch is replicated, each shard scores/updates the
users it owns and the per-request results are combined with one `psum`
(non-owner shards contribute zeros).  Refresh runs `stages.stage2_refresh`
with the mesh collectives, i.e. the identical code path as
`distributed.distclub_shard`.  A serving replica set is the offline
sharded runtime plus a request front-end.

Fault tolerance: `session.save(ckpt, step)` / `session.restore(ckpt)`
round-trip the policy state through `train.checkpoint.CheckpointManager`
(re-sharded onto whatever mesh the restoring session has) — a restarted
replica resumes with bit-identical subsequent choices
(`tests/test_serve.py::test_checkpoint_restore_resumes_bit_identical`).

Caching note: compiled transactions are memoized per (policy, reward_fn,
mesh) — pass a *stable* `reward_fn` (a module-level function or one
closure built once), not a fresh lambda per call, or every call retraces.

Padding contract (load-bearing for `serve.experiments`): rows with
``uid < 0`` or ``uid >= n_users`` flow through every transaction as
no-ops — choice 0 / item -1, no state change, decision id -1.  The
experiment router exploits this to partition one batch across N arm
sessions by masking non-assigned rows to uid -1, which keeps a
single-arm experiment bit-identical to a plain session.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core import catalog as catalog_mod
from ..core import itemclub as itemclub_mod
from ..core.backend import BackendConfig
from ..core.types import BanditHyper, Metrics
from ..kernels.topk.ref import select_topk
from ..runtime.collectives import NullCollectives, lax_collectives
from . import pending as pending_mod
from . import policies as pol

_NULL = NullCollectives()

# the Precision policy is checkpointed as a small i32 tag (dtype codes +
# scale block) so restore can refuse a snapshot written under another one
_PREC_NAMES = ("f32", "bf16", "int8")


def _precision_tag(prec):
    return jnp.array([_PREC_NAMES.index(prec.state_dtype),
                      _PREC_NAMES.index(prec.catalog_dtype),
                      _PREC_NAMES.index(prec.accum_dtype),
                      prec.scale_block], jnp.int32)


def _decode_precision_tag(codes):
    def name(c):
        return _PREC_NAMES[c] if 0 <= c < len(_PREC_NAMES) else f"?{c}"

    return (f"Precision(state={name(codes[0])}, catalog={name(codes[1])}, "
            f"accum={name(codes[2])}, scale_block={codes[3]})")


def embed_candidates(item_embed: jnp.ndarray, cand_ids: jnp.ndarray):
    """Model item embeddings -> unit-norm bandit contexts [B, K, d]."""
    e = item_embed[cand_ids]
    return e / jnp.maximum(jnp.linalg.norm(e, axis=-1, keepdims=True), 1e-9)


# ---------------------------------------------------------------------------
# the transaction body (shared single-host / sharded)
# ---------------------------------------------------------------------------


def _occurrence_ranks(user_ids: jnp.ndarray) -> jnp.ndarray:
    """rank[i] = number of earlier batch items with the same user id.
    O(B^2) bools — negligible next to the [B, d, d] row gathers at
    serving batch sizes."""
    eq = user_ids[:, None] == user_ids[None, :]
    earlier = jnp.tril(eq, k=-1)
    return jnp.sum(earlier, axis=1).astype(jnp.int32)


def _normalize_rewards(out):
    """Accept `realized [B]` or the full env 4-tuple
    `(realized, expected, best, rand)`; missing regret/baseline terms
    metric as zero."""
    if isinstance(out, (tuple, list)):
        realized, expected, best, rand = out
    else:
        realized = out
        expected = best = rand = jnp.zeros_like(realized)
    return realized, expected, best, rand


def _request_masks(policy, col, state, user_ids):
    """(idx, own, valid, be): local row index per request, ownership mask
    for this shard, global validity, and the batch-width engine (the
    session's run-level dispatch re-fit to this traced batch width)."""
    cfg = policy.cfg
    n_local = policy.occ_of(state).shape[0]
    row0 = col.axis_index() * n_local
    valid = (user_ids >= 0) & (user_ids < cfg.n_users)
    local = user_ids - row0
    own = valid & (local >= 0) & (local < n_local)
    idx = jnp.clip(local, 0, n_local - 1)
    return idx, own, valid, cfg.engine.with_users(user_ids.shape[0])


def _choose(policy, col, state, user_ids, contexts):
    """Score + fused choose; combine per-request results across shards."""
    idx, own, valid, be = _request_masks(policy, col, state, user_ids)
    w, minv_eff, occ_rows = policy.gather_score(state, idx)
    x, choice = be.choose(w, minv_eff, contexts, occ_rows,
                          policy.cfg.hyper.alpha)
    choice = col.psum(jnp.where(own, choice, 0))
    x = col.psum(jnp.where(own[:, None], x, jnp.zeros_like(x)))
    return choice, x, (idx, own, valid, be)

def _fold_feedback(policy, state, idx, own, valid, be, user_ids, x,
                   realized):
    """Duplicate-safe feedback fold: one fused masked pass per occurrence
    rank (live rows of a pass are distinct users -> the pass is exact;
    distinct-user batches take exactly one pass)."""
    ranks = _occurrence_ranks(user_ids)
    n_passes = jnp.max(jnp.where(valid, ranks, -1)) + 1

    def one_pass(k, st):
        live = own & (ranks == k)
        return policy.apply_pass(st, idx, x, realized, live, be)

    return jax.lax.fori_loop(0, n_passes, one_pass, state)


def _schedule_refresh(policy, col, state, n_new, key):
    """Trace-friendly refresh: `lax.cond` on the interaction budget.

    The refresh key mixes the state's lifetime interaction count into the
    caller's key, so a randomized refresh (dccb gossip's peer draw) still
    varies round to round even when the caller reuses a key — e.g. the
    `observe` half's default.  The count is part of the checkpointed
    state, so a restored replica replays the identical schedule."""
    since = state.since_refresh + n_new
    state = state._replace(since_refresh=since)
    every = policy.cfg.refresh_every
    if not policy.has_refresh or every <= 0:
        return state
    k_ref = jax.random.fold_in(jax.random.fold_in(key, 1),
                               col.psum(jnp.sum(policy.occ_of(state))))

    def fire(st):
        st = policy.refresh(col, st, k_ref)
        return st._replace(since_refresh=jnp.zeros((), jnp.int32))

    return jax.lax.cond(since >= every, fire, lambda st: st, state)


def _apply_feedback(policy, col, state, key, idx, own, valid, be,
                    user_ids, x, rewards):
    """The shared transaction tail of both step bodies: fold the reward
    4-tuple, run the refresh schedule, reduce the batch metrics."""
    realized, expected, best, rand = rewards
    state = _fold_feedback(policy, state, idx, own, valid, be, user_ids,
                           x, realized)
    n_new = jnp.sum(valid.astype(jnp.int32))
    state = _schedule_refresh(policy, col, state, n_new, key)
    vm = valid.astype(realized.dtype)
    metrics = Metrics(
        reward=jnp.sum(realized * vm),
        regret=jnp.sum((best - expected) * vm),
        rand_reward=jnp.sum(rand * vm),
        interactions=n_new,
    )
    return state, metrics


def _step_body(policy, reward_fn, col, state, key, user_ids, contexts):
    choice, x, (idx, own, valid, be) = _choose(policy, col, state,
                                               user_ids, contexts)
    rewards = _normalize_rewards(reward_fn(key, user_ids, contexts, choice))
    state, metrics = _apply_feedback(policy, col, state, key, idx, own,
                                     valid, be, user_ids, x, rewards)
    return state, choice, metrics


def _observe_body(policy, col, state, key, user_ids, contexts, choices,
                  rewards):
    idx, own, valid, be = _request_masks(policy, col, state, user_ids)
    x = jnp.take_along_axis(contexts, choices[:, None, None], axis=1)[:, 0]
    state = _fold_feedback(policy, state, idx, own, valid, be, user_ids,
                           x, rewards)
    n_new = jnp.sum(valid.astype(jnp.int32))
    return _schedule_refresh(policy, col, state, n_new, key)


# ---------------------------------------------------------------------------
# catalog-scale retrieval: shortlist -> merge -> fused choose
# ---------------------------------------------------------------------------


def _catalog_choose(policy, rb, col, state, user_ids, catalog,
                    clusters=None):
    """Two-stage choose against a persistent (item-sharded) catalog.

    Stage 1 (shortlist): the request users' statistics are psum-replicated
    to every shard, each shard runs the streaming top-K engine over its
    LOCAL catalog slice, and the per-shard ``[B, K_short]`` (score, id)
    lists are all-gathered and merged by (score desc, id asc) — the exact
    order the kernel itself selects in, so the merged list is bit-equal
    to a single-host shortlist over the whole catalog (comm:
    ``O(B K_short shards)`` words, never ``O(B N_items)``).

    With ``clusters`` (a replicated ``core.itemclub.ItemClusters``) stage
    1 runs CLUSTER-PRUNED: each shard streams its position range of the
    cluster-sorted catalog and skips tiles whose UCB upper bound cannot
    beat the running shortlist floor — EXACT (the shortlist is bit-equal
    to the unpruned one; ``kernels/topk/ref.py``), and since the sorted
    stream carries global slot ids, the per-shard merge is too.  The
    churn-safety rule is enforced HERE, inside the jit transaction: if
    the cluster table's epoch does not match the catalog's (a `publish`
    landed after the last rebuild), the whole batch falls back to the
    unpruned stream — stale bounds are never trusted.  The last returned
    value is then a ``RetrievalMetrics`` (psum-combined tile skip counts
    + whether pruning was active); None when no clusters were given.

    Stage 2 (choose): shortlist embeddings are assembled by a one-hot
    psum (each shard contributes the rows it owns) and ranked by the
    session's fused ``InteractBackend.choose`` re-fit to ``K_short``
    candidates.  Underfull slots (score -inf) are filled with the user's
    top entry, so the filler can never outrank a real candidate and maps
    back to a valid item id.  For ``N_items <= K_short`` the shortlist is
    the whole catalog in (score desc, id asc) order and the chosen item
    is bit-identical to scoring the catalog as one direct slate.
    """
    cfg = policy.cfg
    idx, own, valid, be = _request_masks(policy, col, state, user_ids)
    w, minv_eff, occ_rows = policy.gather_score(state, idx)
    # replicate the request rows: exactly one shard owns each valid user
    w = col.psum(jnp.where(own[:, None], w, 0.0))
    minv_eff = col.psum(jnp.where(own[:, None, None], minv_eff, 0.0))
    occ_rows = col.psum(jnp.where(own, occ_rows, 0))

    bank = catalog.serving            # the ACTIVE double-buffer bank
    n_local_items = bank.live.shape[0]
    row0_items = col.axis_index() * n_local_items
    # int8 banks ship their per-slot dequant scales into the kernels;
    # f32/bf16 banks upcast in VMEM without scales (trace-time branch)
    scales = bank.scale if bank.emb.dtype == jnp.int8 else None
    if clusters is None:
        sc, ids = rb.shortlist(w, minv_eff, occ_rows, bank.emb, bank.live,
                               cfg.hyper.alpha, row0_items=row0_items,
                               scales=scales)
        rmet = None
    else:
        shard_tabs = itemclub_mod.shard_slice(clusters, col.axis_index(),
                                              n_local_items)
        fresh = clusters.epoch == catalog.epoch

        def _pruned(_):
            (emb_s, live_s, ids_s, scale_s,
             t_mu, t_r, t_xn, t_n) = shard_tabs
            ss = scale_s if emb_s.dtype == jnp.int8 else None
            return rb.shortlist_pruned(w, minv_eff, occ_rows, emb_s,
                                       live_s, ids_s, t_mu, t_r, t_xn,
                                       t_n, cfg.hyper.alpha,
                                       scales_sorted=ss)

        def _unpruned(_):
            s, i = rb.shortlist(w, minv_eff, occ_rows, bank.emb,
                                bank.live, cfg.hyper.alpha,
                                row0_items=row0_items, scales=scales)
            z = jnp.zeros((), jnp.int32)
            return s, i, z, z

        sc, ids, skipped, total = jax.lax.cond(fresh, _pruned, _unpruned,
                                               None)
        rmet = itemclub_mod.RetrievalMetrics(
            tiles_skipped=col.psum(skipped),
            tiles_total=col.psum(total),
            pruned_active=fresh.astype(jnp.int32),
        )
    sc_all = col.all_gather(sc[None])           # [S, B, K_short]
    id_all = col.all_gather(ids[None])
    B = user_ids.shape[0]
    sc_flat = jnp.moveaxis(sc_all, 0, 1).reshape(B, -1)
    id_flat = jnp.moveaxis(id_all, 0, 1).reshape(B, -1)
    # merge with the kernel's OWN selection routine, so the merged order
    # is the kernel's order by construction (not a re-implementation
    # that could diverge on e.g. signed-zero ties)
    top_s, top_i = select_topk(sc_flat, id_flat, rb.K_short)
    top_i = jnp.where(jnp.isfinite(top_s), top_i, top_i[:, :1])

    loc = top_i - row0_items
    ok = (loc >= 0) & (loc < n_local_items)
    g = jnp.clip(loc, 0, n_local_items - 1)
    # dequantize the gathered shortlist rows before the f32 psum — the
    # slate the fused choose (and the reward_fn) sees is always f32
    rows = bank.emb[g].astype(jnp.float32)
    if scales is not None:
        rows = rows * bank.scale[g][..., None]
    ctx = col.psum(jnp.where(ok[..., None], rows, 0.0))   # [B, K_short, d]

    be_s = be.with_candidates(rb.K_short)
    x, slot = be_s.choose(w, minv_eff, ctx, occ_rows, cfg.hyper.alpha)
    item = jnp.take_along_axis(top_i, slot[:, None], axis=1)[:, 0]
    item = jnp.where(valid, item, -1)
    return item, slot, ctx, x, (idx, own, valid, be), rmet


def _catalog_step_body(policy, rb, reward_fn, col, state, key, user_ids,
                       catalog, clusters=None):
    item, slot, ctx, x, (idx, own, valid, be), rmet = _catalog_choose(
        policy, rb, col, state, user_ids, catalog, clusters)
    rewards = _normalize_rewards(reward_fn(key, user_ids, ctx, slot))
    state, metrics = _apply_feedback(policy, col, state, key, idx, own,
                                     valid, be, user_ids, x, rewards)
    if clusters is None:
        return state, item, metrics
    return state, item, metrics, rmet


# ---------------------------------------------------------------------------
# the pending-decision feedback loop: issue now, fold when feedback lands
# ---------------------------------------------------------------------------


def _issue_body(policy, ttl, col, state, pend, user_ids, contexts):
    """The request half on a buffer-enabled session: choose (identical
    math to `_step_body`) and enqueue one pending decision per valid
    request.  The policy state is read, never written."""
    choice, x, (idx, own, valid, be) = _choose(policy, col, state,
                                               user_ids, contexts)
    pend, ids = pending_mod.issue(pend, user_ids, choice, x, valid, ttl)
    return pend, choice, ids


def _catalog_issue_body(policy, rb, ttl, col, state, pend, user_ids,
                        catalog, clusters=None):
    item, slot, ctx, x, (idx, own, valid, be), rmet = _catalog_choose(
        policy, rb, col, state, user_ids, catalog, clusters)
    pend, ids = pending_mod.issue(pend, user_ids, item, x, valid, ttl,
                                  epoch=catalog.epoch)
    if clusters is None:
        return pend, item, ids, slot, ctx
    return pend, item, ids, slot, ctx, rmet


def _observe_delayed_body(policy, col, state, pend, key, decision_ids,
                          rewards, stale=None):
    """Fold feedback matched by decision id: the matched slots supply the
    exact (uid, chosen-context) pair the synchronous fold would have
    used, so the delayed fold is bit-identical; unmatched entries
    (expired / already folded / in-batch duplicates / id -1 padding)
    surface as uid -1 and fold as padding, and ``stale``-masked entries
    are quarantined by the match (freed + counted, never folded)."""
    pend, uids, x = pending_mod.match(pend, decision_ids, stale=stale)
    idx, own, valid, be = _request_masks(policy, col, state, uids)
    state = _fold_feedback(policy, state, idx, own, valid, be, uids, x,
                           rewards)
    n_new = jnp.sum(valid.astype(jnp.int32))
    state = _schedule_refresh(policy, col, state, n_new, key)
    return state, pend


def _stale_mask(col, pend, decision_ids, catalog):
    """Per-delivery staleness against the CURRENT catalog: feedback for a
    decision issued at epoch ``e`` folds iff the published epoch is at
    most ``e + 1`` (the one-stale-epoch bound) AND its item is still
    live in the active bank with ``born <= e`` (a retired-then-reclaimed
    slot fails the born check even though it is live again).  Item
    liveness is resolved per item shard and psum-combined, mirroring the
    shortlist-row assembly.  Values at non-resident slots are garbage —
    harmless, since ``match`` only applies the mask to hits."""
    C = pend.uid.shape[0]
    slot = jnp.mod(jnp.where(decision_ids >= 0, decision_ids, 0), C)
    item = pend.choice[slot]
    e_issue = pend.epoch[slot]
    bank = catalog.serving
    n_local = bank.live.shape[0]
    row0 = col.axis_index() * n_local
    loc = item - row0
    in_range = (loc >= 0) & (loc < n_local)
    li = jnp.clip(loc, 0, n_local - 1)
    ok_here = in_range & (bank.live[li] > 0) & (bank.born[li] <= e_issue)
    item_ok = col.psum(ok_here.astype(jnp.int32)) > 0
    fresh = (catalog.epoch - e_issue) <= 1
    return ~(item_ok & fresh)


def _observe_delayed_catalog_body(policy, col, state, pend, key,
                                  decision_ids, rewards, catalog):
    stale = _stale_mask(col, pend, decision_ids, catalog)
    return _observe_delayed_body(policy, col, state, pend, key,
                                 decision_ids, rewards, stale=stale)


def _refresh_body(policy, col, state, key):
    k_ref = jax.random.fold_in(key,
                               col.psum(jnp.sum(policy.occ_of(state))))
    state = policy.refresh(col, state, k_ref)
    return state._replace(since_refresh=jnp.zeros((), jnp.int32))


# ---------------------------------------------------------------------------
# compiled-transaction cache (per policy / reward_fn / mesh)
# ---------------------------------------------------------------------------


def _bind_tx(policy, body, mesh, axes, out_extra=(), out_override=None):
    """jit `body(col, state, *args)` — single-host with NullCollectives,
    or shard_map'd over `mesh` with the policy's state specs (request
    args and scalar/choice outputs replicated)."""
    if mesh is None:
        return jax.jit(functools.partial(body, _NULL))
    col = lax_collectives(mesh, axes)
    specs = policy.state_specs(axes)
    bound = functools.partial(body, col)
    if out_override is not None:
        out_specs = out_override
    elif out_extra:
        out_specs = (specs,) + tuple(out_extra)
    else:
        out_specs = specs

    def wrap(state, *args):
        mapped = jax.shard_map(
            bound, mesh=mesh,
            in_specs=(specs,) + tuple(P() for _ in args),
            out_specs=out_specs,
            check_vma=False,
        )
        return mapped(state, *args)

    return jax.jit(wrap)


@functools.lru_cache(maxsize=64)
def _step_fn(policy, reward_fn, mesh, axes):
    body = functools.partial(_step_body, policy, reward_fn)
    return _bind_tx(policy, body, mesh, axes,
                    out_extra=(P(), Metrics(P(), P(), P(), P())))


@functools.lru_cache(maxsize=64)
def _recommend_fn(policy, mesh, axes):
    def body(col, state, user_ids, contexts):
        choice, _, _ = _choose(policy, col, state, user_ids, contexts)
        return choice
    return _bind_tx(policy, body, mesh, axes, out_override=P())


@functools.lru_cache(maxsize=64)
def _observe_fn(policy, mesh, axes):
    def body(col, state, key, user_ids, contexts, choices, rewards):
        return _observe_body(policy, col, state, key, user_ids, contexts,
                             choices, rewards)
    return _bind_tx(policy, body, mesh, axes)


def _bind_catalog_tx(policy, body, mesh, axes, n_plain, out_specs,
                     tail_specs=()):
    """Like ``_bind_tx`` but the trailing arguments after the ``n_plain``
    replicated request inputs are a Catalog sharded on the ITEM axis over
    the same mesh axes the user state shards on, then any ``tail_specs``
    extras (e.g. a replicated ``ItemClusters`` on the pruned path)."""
    if mesh is None:
        return jax.jit(functools.partial(body, _NULL))
    col = lax_collectives(mesh, axes)
    bound = functools.partial(body, col)
    in_specs = ((policy.state_specs(axes),)
                + tuple(P() for _ in range(n_plain))
                + (catalog_mod.specs(axes),) + tuple(tail_specs))

    def wrap(state, *args):
        mapped = jax.shard_map(
            bound, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False,
        )
        return mapped(state, *args)

    return jax.jit(wrap)


_RMET_SPECS = itemclub_mod.RetrievalMetrics(P(), P(), P())


@functools.lru_cache(maxsize=64)
def _catalog_step_fn(policy, rb, reward_fn, mesh, axes, pruned=False):
    body = functools.partial(_catalog_step_body, policy, rb, reward_fn)
    out = ((policy.state_specs(axes) if mesh is not None else None),
           P(), Metrics(P(), P(), P(), P()))
    if pruned:
        out = out + (_RMET_SPECS,)
    return _bind_catalog_tx(policy, body, mesh, axes, n_plain=2,
                            out_specs=out,
                            tail_specs=((itemclub_mod.specs(),)
                                        if pruned else ()))


@functools.lru_cache(maxsize=64)
def _catalog_recommend_fn(policy, rb, mesh, axes, pruned=False):
    def body(col, state, user_ids, catalog, clusters=None):
        item, slot, ctx, _, _, rmet = _catalog_choose(
            policy, rb, col, state, user_ids, catalog, clusters)
        if clusters is None:
            return item, slot, ctx
        return item, slot, ctx, rmet
    out = (P(), P(), P()) + ((_RMET_SPECS,) if pruned else ())
    return _bind_catalog_tx(policy, body, mesh, axes, n_plain=1,
                            out_specs=out,
                            tail_specs=((itemclub_mod.specs(),)
                                        if pruned else ()))


def _bind_pending_tx(policy, body, mesh, axes, n_plain, out_specs, *,
                     catalog=False, tail_specs=()):
    """Like ``_bind_tx`` for bodies over ``(state, pending, *args)`` —
    the pending buffer is replicated; with ``catalog`` the LAST plain
    arg is instead an item-sharded Catalog, and ``tail_specs`` extras
    (replicated cluster tables) follow it."""
    if mesh is None:
        return jax.jit(functools.partial(body, _NULL))
    col = lax_collectives(mesh, axes)
    bound = functools.partial(body, col)
    plain = [P() for _ in range(n_plain)]
    if catalog:
        plain[-1] = catalog_mod.specs(axes)
    in_specs = ((policy.state_specs(axes), pending_mod.specs())
                + tuple(plain) + tuple(tail_specs))

    def wrap(state, *args):
        mapped = jax.shard_map(
            bound, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False,
        )
        return mapped(state, *args)

    return jax.jit(wrap)


@functools.lru_cache(maxsize=64)
def _issue_fn(policy, ttl, mesh, axes):
    body = functools.partial(_issue_body, policy, ttl)
    return _bind_pending_tx(policy, body, mesh, axes, n_plain=2,
                            out_specs=(pending_mod.specs(), P(), P()))


@functools.lru_cache(maxsize=64)
def _catalog_issue_fn(policy, rb, ttl, mesh, axes, pruned=False):
    body = functools.partial(_catalog_issue_body, policy, rb, ttl)
    out = (pending_mod.specs(), P(), P(), P(), P())
    if pruned:
        out = out + (_RMET_SPECS,)
    return _bind_pending_tx(
        policy, body, mesh, axes, n_plain=2, out_specs=out,
        catalog=True,
        tail_specs=(itemclub_mod.specs(),) if pruned else ())


@functools.lru_cache(maxsize=64)
def _observe_delayed_fn(policy, mesh, axes):
    def body(col, state, pend, key, decision_ids, rewards):
        return _observe_delayed_body(policy, col, state, pend, key,
                                     decision_ids, rewards)
    out = (policy.state_specs(axes) if mesh is not None else None,
           pending_mod.specs())
    return _bind_pending_tx(policy, body, mesh, axes, n_plain=3,
                            out_specs=out)


@functools.lru_cache(maxsize=64)
def _observe_delayed_catalog_fn(policy, mesh, axes):
    def body(col, state, pend, key, decision_ids, rewards, catalog):
        return _observe_delayed_catalog_body(policy, col, state, pend,
                                             key, decision_ids, rewards,
                                             catalog)
    out = (policy.state_specs(axes) if mesh is not None else None,
           pending_mod.specs())
    return _bind_pending_tx(policy, body, mesh, axes, n_plain=4,
                            out_specs=out, catalog=True)


@functools.lru_cache(maxsize=64)
def _force_refresh_fn(policy, mesh, axes):
    def body(col, state, key):
        return _refresh_body(policy, col, state, key)
    return _bind_tx(policy, body, mesh, axes)


# ---------------------------------------------------------------------------
# the session object + functional API
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class OnlineBandit:
    """One serving session: a hashable policy (static) + its state
    (pytree) + optional mesh binding.  Immutable — `step`/`observe`
    return a new session wrapping the new state."""

    policy: Any
    state: Any
    mesh: Any = None
    axes: tuple = ()
    pending: Any = None     # PendingBuffer, or None = synchronous-only
    ttl: int = 0            # pending TTL in issue transactions (static)

    # -- construction ------------------------------------------------------
    @classmethod
    def create(cls, n_users: int, d: int, hyper: BanditHyper, *,
               policy: str = "distclub", refresh_every: int = 0,
               backend: str | None = None, interpret: bool | None = None,
               block_users: int = 256, pending_capacity: int = 0,
               pending_ttl: int = 64, precision=None) -> "OnlineBandit":
        """Single-host session.  `refresh_every` is the interaction budget
        between refreshes (stage-2 / gossip); <= 0 disables scheduling
        (use `serve.refresh` to fire one manually).  `pending_capacity`
        > 0 enables the fault-tolerant feedback loop: `recommend`
        issues + enqueues and `observe_delayed` folds feedback by
        decision id; `pending_ttl` is how many SUBSEQUENT recommend
        transactions a decision survives before its feedback is dropped
        as expired.  `precision` (a `core.backend.Precision`, a preset
        name, or None = `REPRO_PRECISION` / f32) picks the reduced-
        precision state policy; checkpoints record it and refuse to
        restore under a different one."""
        cfg = pol.make_cfg(n_users, d, hyper, refresh_every=refresh_every,
                           backend=backend, interpret=interpret,
                           block_users=block_users, precision=precision)
        p = pol.get_policy(policy, cfg)
        pend = (pending_mod.init(pending_capacity, d)
                if pending_capacity > 0 else None)
        return cls(policy=p, state=p.init(), pending=pend,
                   ttl=int(pending_ttl))

    @classmethod
    def sharded(cls, mesh, n_users: int, d: int, hyper: BanditHyper, *,
                axes: tuple[str, ...] | None = None,
                policy: str = "distclub", refresh_every: int = 0,
                backend: str | None = None, interpret: bool | None = None,
                block_users: int = 256, pending_capacity: int = 0,
                pending_ttl: int = 64, precision=None) -> "OnlineBandit":
        """Serving replica set: per-user state sharded over `mesh` (users
        on the flattened `axes`), request batches replicated, refresh on
        the mesh collectives — the identical stage-2 code path as
        `distributed.distclub_shard`."""
        from ..distributed.distclub_shard import named_shardings

        axes = tuple(axes) if axes is not None else tuple(mesh.axis_names)
        cfg = pol.make_cfg(n_users, d, hyper, refresh_every=refresh_every,
                           backend=backend, interpret=interpret,
                           block_users=block_users, precision=precision)
        p = pol.get_policy(policy, cfg)
        shards = 1
        for a in axes:
            shards *= mesh.shape[a]
        if n_users % shards:
            raise ValueError(
                f"the {shards}-way mesh must evenly divide n_users={n_users}")
        state = jax.device_put(
            p.init(), named_shardings(mesh, p.state_specs(axes)))
        pend = (pending_mod.init(pending_capacity, d)
                if pending_capacity > 0 else None)
        return cls(policy=p, state=state, mesh=mesh, axes=axes,
                   pending=pend, ttl=int(pending_ttl))

    @classmethod
    def from_offline(cls, state, hyper: BanditHyper, *,
                     refresh_every: int = 0, backend: str | None = None,
                     interpret: bool | None = None,
                     precision=None) -> "OnlineBandit":
        """Warm-start a distclub serving session from an offline
        `distclub.run` final state (f32 — downcast into the session's
        precision state dtype here, a no-op under f32)."""
        n, d = state.lin.b.shape
        cfg = pol.make_cfg(n, d, hyper, refresh_every=refresh_every,
                           backend=backend, interpret=interpret,
                           precision=precision)
        p = pol.get_policy("distclub", cfg)
        st = pol.from_distclub_state(state)
        sdt = cfg.engine.precision.jnp_state
        st = st._replace(Minv=st.Minv.astype(sdt),
                         uMcinv=st.uMcinv.astype(sdt))
        return cls(policy=p, state=st)

    # -- checkpointing -----------------------------------------------------
    def _shardings(self):
        if self.mesh is None:
            return None
        from ..distributed.distclub_shard import named_shardings
        return named_shardings(self.mesh,
                               self.policy.state_specs(self.axes))

    def _precision_tag(self):
        return _precision_tag(self.policy.cfg.engine.precision)

    def _ckpt_shardings(self):
        sh = self._shardings()
        if sh is None:
            return None
        from jax.sharding import NamedSharding
        return {"prec": NamedSharding(self.mesh, P()), "state": sh}

    def save(self, ckpt, step: int):
        """Snapshot the policy state (atomic, keep-K — see
        `train.checkpoint`).  The session's `Precision` policy is
        recorded alongside the state: a reduced-precision snapshot is not
        silently reinterpretable, so `restore` refuses a mismatch."""
        payload = {"prec": self._precision_tag(), "state": self.state}
        return ckpt.save(payload, step)

    def restore(self, ckpt, step: int | None = None):
        """(session, step) restored from `ckpt` (latest when `step` is
        None; (self, None) when the directory is empty).  Re-shards onto
        this session's mesh — a replica restarted on a different mesh
        resumes from the same bytes.  Raises ``ValueError`` when the
        checkpoint was written under a different `Precision` policy —
        bytes saved as bf16/int8 state must not be silently upcast into
        an f32 session (or vice versa)."""
        like = {"prec": self._precision_tag(), "state": self.state}
        shardings = self._ckpt_shardings()
        if step is None:
            payload, step = ckpt.restore_latest(like, shardings)
            if payload is None:
                return self, None
        else:
            payload = ckpt.restore(step, like, shardings)
        got = [int(v) for v in jax.device_get(payload["prec"])]
        want = [int(v) for v in jax.device_get(self._precision_tag())]
        if got != want:
            raise ValueError(
                f"checkpoint precision mismatch: step {step} was saved "
                f"under {_decode_precision_tag(got)} but this session "
                f"runs {_decode_precision_tag(want)} — recreate the "
                "session with the matching precision= (or re-train)")
        return dataclasses.replace(self, state=payload["state"]), step

    # -- the transaction and its halves ------------------------------------
    def step(self, key, user_ids, contexts, reward_fn):
        return step(self, key, user_ids, contexts, reward_fn)

    def recommend(self, user_ids, contexts):
        return recommend(self, user_ids, contexts)

    def step_catalog(self, key, user_ids, catalog, reward_fn, *,
                     k_short: int = 64, clusters=None):
        return step_catalog(self, key, user_ids, catalog, reward_fn,
                            k_short=k_short, clusters=clusters)

    def recommend_catalog(self, user_ids, catalog, *, k_short: int = 64,
                          clusters=None):
        return recommend_catalog(self, user_ids, catalog, k_short=k_short,
                                 clusters=clusters)

    def observe(self, user_ids, contexts, choices, rewards, key=None):
        return observe(self, user_ids, contexts, choices, rewards, key=key)

    def observe_delayed(self, decision_ids, rewards, key=None,
                        catalog=None):
        return observe_delayed(self, decision_ids, rewards, key=key,
                               catalog=catalog)

    def reset_pending(self):
        return reset_pending(self)

    def refresh(self, key=None):
        return refresh(self, key=key)


def step(session: OnlineBandit, key, user_ids, contexts,
         reward_fn: Callable):
    """One jit-compiled serving transaction.

    `user_ids [B] i32` (ids < 0 or >= n_users are ignored — padding),
    `contexts [B, K, d]`, `reward_fn(key, user_ids, contexts, choices)`
    returning realized rewards `[B]` or the full environment 4-tuple
    `(realized, expected, best, rand)`.  Returns
    `(session, choices [B], metrics)` — `metrics` rows for terms the
    reward_fn didn't supply are zero.  `key` drives the reward draw
    as-given (and, folded, the dccb gossip refresh)."""
    fn = _step_fn(session.policy, reward_fn, session.mesh, session.axes)
    state, choices, metrics = fn(session.state, key, user_ids, contexts)
    return dataclasses.replace(session, state=state), choices, metrics


def _pending_guard(session: OnlineBandit, B: int):
    cap = session.pending.uid.shape[0]
    if B > cap:
        raise ValueError(
            f"pending capacity {cap} < batch width {B}: a batch of "
            "consecutive decision ids must land on distinct ring slots — "
            "create the session with pending_capacity >= the largest "
            "request batch")


def recommend(session: OnlineBandit, user_ids, contexts):
    """The request half: choices `[B]` for a batch.

    On a synchronous session (no pending buffer) this is pure — returns
    just `choices [B]`.  On a buffer-enabled session it ISSUES: returns
    `(session, choices [B], decision_ids [B])`, enqueuing one pending
    decision per valid request (padding requests get decision id -1);
    feed the ids to :func:`observe_delayed` when feedback arrives."""
    if session.pending is None:
        fn = _recommend_fn(session.policy, session.mesh, session.axes)
        return fn(session.state, user_ids, contexts)
    _pending_guard(session, user_ids.shape[0])
    fn = _issue_fn(session.policy, session.ttl, session.mesh, session.axes)
    pend, choices, ids = fn(session.state, session.pending, user_ids,
                            contexts)
    return dataclasses.replace(session, pending=pend), choices, ids


def observe(session: OnlineBandit, user_ids, contexts, choices, rewards,
            key=None):
    """The feedback half: fold a batch of (possibly duplicate-user)
    rewards and run the refresh schedule.  `key` is only consumed by the
    dccb gossip refresh (defaults to a fixed key)."""
    if key is None:
        key = jax.random.PRNGKey(0)
    fn = _observe_fn(session.policy, session.mesh, session.axes)
    state = fn(session.state, key, user_ids, contexts, choices, rewards)
    return dataclasses.replace(session, state=state)


def _retrieval_engine(session: OnlineBandit, k_short: int):
    """The session's retrieval backend: dispatch (kind/interpret) follows
    the run-level interact engine, resolved once per (session, k_short)."""
    eng = session.policy.cfg.engine
    return BackendConfig(kind=eng.kind, precision=eng.precision).retrieval(
        eng.d, k_short, interpret=eng.interpret)


def step_catalog(session: OnlineBandit, key, user_ids, catalog,
                 reward_fn: Callable, *, k_short: int = 64, clusters=None):
    """One serving transaction against a persistent catalog.

    Like :func:`step`, but the slate is not supplied by the caller — it
    is retrieved: each user's ``k_short`` highest-UCB live items are
    shortlisted by the streaming top-K engine (per item shard on a
    sharded session) and the fused choose ranks the shortlist.

    ``catalog`` is a ``core.catalog.Catalog``; on a sharded session it
    must be device_put item-sharded over the session mesh
    (``catalog.specs(axes)``) with ``capacity % shards == 0``.
    ``reward_fn(key, user_ids, contexts, choice)`` sees the
    ``[B, k_short, d]`` shortlist slate and the chosen SLOT — the same
    contract as :func:`step` — so regret terms are relative to the
    shortlist's best.  Returns ``(session, item_ids [B], metrics)`` with
    GLOBAL catalog ids (-1 for padded requests).

    ``clusters`` — a ``core.itemclub.ItemClusters`` built from this
    catalog enables CLUSTER-PRUNED retrieval: item tiles whose UCB upper
    bound cannot beat the running shortlist floor are skipped, with the
    chosen items BIT-IDENTICAL to the unpruned path.  A stale table
    (``clusters.epoch != catalog.epoch`` after a `publish`) falls back
    to the unpruned stream inside the transaction — rebuild on the
    stage-2 cadence with ``itemclub.refresh_clusters``.  The return
    gains a trailing ``RetrievalMetrics`` (tile skip counts +
    ``pruned_active``).  The cluster tables are replicated — pass them
    as-is on a sharded session (``capacity % (tile_items * shards)``
    must be 0).
    """
    rb = _retrieval_engine(session, k_short)
    fn = _catalog_step_fn(session.policy, rb, reward_fn, session.mesh,
                          session.axes, clusters is not None)
    if clusters is None:
        state, item_ids, metrics = fn(session.state, key, user_ids,
                                      catalog)
        return dataclasses.replace(session, state=state), item_ids, metrics
    state, item_ids, metrics, rmet = fn(session.state, key, user_ids,
                                        catalog, clusters)
    return (dataclasses.replace(session, state=state), item_ids, metrics,
            rmet)


def recommend_catalog(session: OnlineBandit, user_ids, catalog, *,
                      k_short: int = 64, clusters=None):
    """The request half against a catalog.

    On a synchronous session: no state change; returns
    ``(item_ids [B], slots [B], contexts [B, k_short, d])`` — feed
    ``(user_ids, contexts, slots, rewards)`` to :func:`observe` to fold
    the feedback, exactly as with a caller-supplied slate.

    On a buffer-enabled session it ISSUES: returns
    ``(session, item_ids [B], decision_ids [B], slots [B],
    contexts [B, k_short, d])`` — the buffer already holds the chosen
    context each decision needs, so only ``(decision_ids, rewards)`` go
    to :func:`observe_delayed`; slots/contexts are returned for reward
    models that score the served slate.

    ``clusters`` enables cluster-pruned retrieval exactly as in
    :func:`step_catalog` (same exactness + stale-epoch fallback) and
    appends a ``RetrievalMetrics`` to either return shape."""
    rb = _retrieval_engine(session, k_short)
    if session.pending is None:
        fn = _catalog_recommend_fn(session.policy, rb, session.mesh,
                                   session.axes, clusters is not None)
        if clusters is None:
            return fn(session.state, user_ids, catalog)
        return fn(session.state, user_ids, catalog, clusters)
    _pending_guard(session, user_ids.shape[0])
    fn = _catalog_issue_fn(session.policy, rb, session.ttl, session.mesh,
                           session.axes, clusters is not None)
    if clusters is None:
        pend, items, ids, slots, ctx = fn(session.state, session.pending,
                                          user_ids, catalog)
        return (dataclasses.replace(session, pending=pend), items, ids,
                slots, ctx)
    pend, items, ids, slots, ctx, rmet = fn(
        session.state, session.pending, user_ids, catalog, clusters)
    return (dataclasses.replace(session, pending=pend), items, ids, slots,
            ctx, rmet)


def observe_delayed(session: OnlineBandit, decision_ids, rewards,
                    key=None, catalog=None):
    """Fold a batch of delayed feedback matched by decision id.

    ``decision_ids [B] i32`` (id -1 = padding), ``rewards [B]`` realized
    rewards aligned with the ids.  Matching is exact under out-of-order
    and duplicate delivery: a folded decision's slot is freed, so
    re-delivery counts ``unmatched`` and never double-folds; feedback for
    TTL-expired decisions is dropped.  Runs the same refresh schedule as
    :func:`observe` (``key`` drives the dccb gossip draw).  Returns the
    updated session; read counters via :func:`pending_stats`.

    ``catalog`` — pass the CURRENT ``core.catalog.Catalog`` on a
    catalog-serving session and churned-item feedback is QUARANTINED:
    a matched decision folds only if its item survived in the active
    bank (live, ``born`` no later than issue) and the published epoch is
    at most one past its issue epoch; anything else frees the slot and
    counts ``stale`` instead.  Without it, feedback folds regardless of
    churn — correct for slate sessions, corrupt under catalog churn
    (the bug the quarantine formalizes).  At zero churn both paths are
    bit-identical."""
    if session.pending is None:
        raise ValueError(
            "observe_delayed needs a buffer-enabled session — create it "
            "with pending_capacity > 0")
    if key is None:
        key = jax.random.PRNGKey(0)
    if catalog is None:
        fn = _observe_delayed_fn(session.policy, session.mesh,
                                 session.axes)
        state, pend = fn(session.state, session.pending, key,
                         decision_ids, rewards)
    else:
        fn = _observe_delayed_catalog_fn(session.policy, session.mesh,
                                         session.axes)
        state, pend = fn(session.state, session.pending, key,
                         decision_ids, rewards, catalog)
    return dataclasses.replace(session, state=state, pending=pend)


def reset_pending(session: OnlineBandit) -> OnlineBandit:
    """Free every pending slot but keep the id counter monotone — used
    after a guardrail rollback so stale in-flight feedback can never
    alias a post-rollback decision."""
    if session.pending is None:
        return session
    return dataclasses.replace(session,
                               pending=pending_mod.clear(session.pending))


def pending_stats(session: OnlineBandit) -> dict[str, float]:
    """Host-side pending-buffer counters (occupancy, matched, unmatched,
    expired, dropped, ...); empty dict on a synchronous session."""
    if session.pending is None:
        return {}
    return pending_mod.stats(session.pending)


def refresh(session: OnlineBandit, key=None):
    """Force one refresh now (stage-2 for the clustered policies, a
    gossip round for dccb, a no-op for linucb) and reset the budget."""
    if key is None:
        key = jax.random.PRNGKey(0)
    fn = _force_refresh_fn(session.policy, session.mesh, session.axes)
    return dataclasses.replace(session, state=fn(session.state, key))
