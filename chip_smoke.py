#!/usr/bin/env python3
"""Chip smoke test: the bandit system's main path, end to end, on a TPU.

    python chip_smoke.py                # one chip: the paper and serve phases
    python chip_smoke.py --four-chips   # four chips: item-sharded serving only

Phases (one process; every phase prints one line of its own):

* ``paper`` — ``distclub.run`` on the paper's synthetic stress set
  (``configs/distclub_paper.py``: 20,480 users, d=25, K=20, Table-2
  hyper-parameters) for 2 full 4-stage epochs on the Pallas engines, then
  DCCB (``core.dccb.run``) for one epoch at the same size; each against the
  ``reference`` engines on the same seed, plus kernel-level parity of
  choose, the rank-1 fold, the edge prune and the CC hop on the final state.
* ``serve`` — an ``OnlineBandit`` of 65,536 users at d=32 over a
  region-structured catalog of 2^20 items, K_short=64, batches of 1,024
  requests: unpruned and cluster-pruned ``step_catalog`` transactions, a
  ``recommend_catalog`` -> ``observe_delayed`` round trip through the
  pending ring, a staged churn + ``publish``, and a forced stage-2
  ``refresh`` at n=65,536; the shortlist and the choice are compared with
  the reference engine on the same state.
* ``four-chips`` (``--four-chips`` only) — the serve traffic on an
  item-sharded ``OnlineBandit.sharded`` session over a 4-chip mesh,
  compared bit for bit with a one-chip session on ``devices[0]``.

Each phase line gives the engine kind, whether the kernels ran compiled,
the first call's seconds (compilation included), steady milliseconds, the
parity result and the device's peak memory.  They are informational, not
benchmark metrics.  The last line of stdout is one JSON object naming the
device.  The script fails (non-zero exit, no JSON line) when JAX finds no
TPU, when an engine resolves to ``reference`` or interpret mode, when a
transaction lowers without a kernel it should run, or when any phase
raises or misses its parity check.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import re
import sys
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

SEED = 0
# serve phase: a deployment sized for one v5e chip (see the module doc)
N_USERS, D, K_SHORT, BATCH, N_ITEMS = 65_536, 32, 64, 1_024, 2 ** 20
USER_SEGMENTS, ITEM_REGIONS = 8, 64
DCCB_L = 16            # DCCB buffer length

# Two f32 programs that compute one score with different summation orders
# (the Pallas kernels on the MXU/VPU, the reference in XLA) may round it
# differently in the last bits.  Where they then pick different candidates
# (or order a shortlist differently) both picks must be ties up to that
# rounding: their scores, recomputed in float64 on the host, may differ by
# at most TIE_TOL relative to the score scale (scores here are O(1);
# f32 rounding over a d=32 contraction is ~1e-6).
TIE_TOL = 1e-5

_KERNEL_NAME = re.compile(r'kernel_name = "(\w+)"')


class SmokeFailure(RuntimeError):
    pass


def _require(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def _line(phase, **kv):
    print(f"phase={phase} " + " ".join(f"{k}={v}" for k, v in kv.items()),
          flush=True)


def _timed(fn, *args, **kw):
    import jax
    t0 = time.perf_counter()
    out = fn(*args, **kw)
    jax.block_until_ready(out)
    return time.perf_counter() - t0, out


def _peak_bytes():
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use", "not-reported")


def _check_engine(phase, eng, interpret):
    _require(eng.kind == "pallas",
             f"{phase}: engine resolved to {eng.kind!r}, not 'pallas'")
    _require(eng.interpret == interpret,
             f"{phase}: engine interpret={eng.interpret}, want {interpret}")


def _check_kernels(phase, what, lowered, want, interpret):
    """The lowered program must hold a tpu_custom_call for every kernel in
    ``want`` (interpret mode lowers kernels to plain HLO: nothing to find)."""
    if interpret:
        return
    got = set(_KERNEL_NAME.findall(lowered.as_text()))
    missing = set(want) - got
    _require(not missing, f"{phase}: {what} lowers without the Pallas "
                          f"kernels {sorted(missing)} (found {sorted(got)})")


def _highest():
    import jax
    return jax.default_matmul_precision("highest")


# ---------------------------------------------------------------------------
# parity helpers: exact, or a near-tie verified in float64
# ---------------------------------------------------------------------------


def _ucb64(w, Minv, x, occ, alpha):
    """float64 UCB scores of rows ``x [..., d]`` for users ``w [.., d]``."""
    w, Minv, x = (np.asarray(a, np.float64) for a in (w, Minv, x))
    quad = np.einsum("...d,...de,...e->...", x, Minv, x)
    widen = np.sqrt(np.log1p(np.asarray(occ, np.float64)))
    return (np.sum(x * w, axis=-1)
            + alpha * np.sqrt(np.maximum(quad, 0.0)) * widen)


def _near_ties(a, b):
    return np.abs(a - b) <= TIE_TOL * np.maximum(1.0, np.maximum(
        np.abs(a), np.abs(b)))


def _choose_parity(phase, be_p, be_r, w, Minv, ctx, occ, alpha):
    """Pallas vs reference choose on one batch: returns the number of
    requests whose picks differ (all verified near-ties)."""
    _, c_p = be_p.choose(w, Minv, ctx, occ, alpha)
    with _highest():
        _, c_r = be_r.choose(w, Minv, ctx, occ, alpha)
    c_p, c_r = np.asarray(c_p), np.asarray(c_r)
    bad = np.nonzero(c_p != c_r)[0]
    if bad.size:
        ctx_b, w_b = np.asarray(ctx)[bad], np.asarray(w)[bad]
        M_b, o_b = np.asarray(Minv)[bad], np.asarray(occ)[bad]
        s_p = _ucb64(w_b, M_b, ctx_b[np.arange(bad.size), c_p[bad]], o_b,
                     alpha)
        s_r = _ucb64(w_b, M_b, ctx_b[np.arange(bad.size), c_r[bad]], o_b,
                     alpha)
        _require(_near_ties(s_p, s_r).all(),
                 f"{phase}: choose differs from the reference beyond "
                 f"rounding for {int((~_near_ties(s_p, s_r)).sum())} users")
    return int(bad.size)


def _shortlist_parity(phase, rb_p, rb_r, w, Minv, occ, emb, live, alpha):
    """Pallas vs reference shortlist ids: equal, or swapped only between
    items whose float64 scores tie up to rounding.  Returns the number of
    swapped positions."""
    _, i_p = rb_p.shortlist(w, Minv, occ, emb, live, alpha)
    with _highest():
        _, i_r = rb_r.shortlist(w, Minv, occ, emb, live, alpha)
    i_p, i_r = np.asarray(i_p), np.asarray(i_r)
    rows, pos = np.nonzero(i_p != i_r)
    if rows.size:
        _require((i_p[rows, pos] >= 0).all() and (i_r[rows, pos] >= 0).all(),
                 f"{phase}: shortlists disagree on which slots hold items")
        e = np.asarray(emb)
        args = (np.asarray(w)[rows], np.asarray(Minv)[rows])
        o = np.asarray(occ)[rows]
        s_p = _ucb64(*args, e[i_p[rows, pos]], o, alpha)
        s_r = _ucb64(*args, e[i_r[rows, pos]], o, alpha)
        _require(_near_ties(s_p, s_r).all(),
                 f"{phase}: shortlist differs from the reference beyond "
                 f"rounding at {int((~_near_ties(s_p, s_r)).sum())} positions")
    return int(rows.size)


def _reward_bound(t_interactions):
    """Largest total-reward gap two valid runs may show once near-tie
    picks have sent some users down different (equally valid)
    trajectories: 6 sigma of a sum of ``t`` Bernoulli draws (sigma <= 0.5
    each) — far below the gap a wrong score or fold would open."""
    return 3.0 * float(np.sqrt(t_interactions))


# ---------------------------------------------------------------------------
# phase: the paper's synthetic set, DistCLUB and DCCB
# ---------------------------------------------------------------------------


def paper_phase(*, n_users=None, epochs=2, interpret=False):
    import jax
    import jax.numpy as jnp
    from repro.configs import distclub_paper as paper
    from repro.core import clustering, dccb, distclub, linucb
    from repro.core.backend import BackendConfig
    from repro.data import datasets

    n = n_users or paper.N_USERS
    hyper, d = paper.CONFIG, paper.D_FEAT
    K = hyper.n_candidates
    spec = dataclasses.replace(datasets.PAPER_DATASETS["synthetic"],
                               n_users=n)
    ops, _ = datasets.make_env(spec, seed=SEED)
    key = jax.random.PRNGKey(SEED)

    pal, ref = BackendConfig.create("pallas"), BackendConfig.create("reference")
    be, gb = pal.interact(n, d, K, interpret=interpret), pal.graph(
        n, interpret=interpret)
    be_r, gb_r = ref.interact(n, d, K), ref.graph(n)
    _check_engine("paper", be, interpret)
    _check_engine("paper", gb, interpret)
    _check_kernels("paper", "distclub.run",
                   distclub._run.lower(ops, key, hyper, epochs, d, be, gb),
                   {"choose", "rank1_update_inv", "graph_prune", "cc_hop"},
                   interpret)

    first_s, _ = _timed(distclub.run, ops, key, hyper, epochs, d,
                        backend=be, graph=gb)
    steady_s, (st, m, ncl) = _timed(distclub.run, ops, key, hyper, epochs,
                                    d, backend=be, graph=gb)
    with _highest():
        _, (st_r, m_r, ncl_r) = _timed(distclub.run, ops, key, hyper,
                                       epochs, d, backend=be_r, graph=gb_r)
    t_p, t_r = int(m.interactions.sum()), int(m_r.interactions.sum())
    r_p, r_r = float(m.reward.sum()), float(m_r.reward.sum())
    _require(np.isfinite(np.asarray(st.lin.Minv)).all(),
             "paper: non-finite Minv after distclub.run")
    _require(abs(r_p - r_r) <= _reward_bound(max(t_p, t_r)),
             f"paper: distclub reward {r_p} vs reference {r_r} exceeds "
             f"{_reward_bound(max(t_p, t_r)):.0f}")

    # kernel-level parity at full width on the run's final state
    lin = st.lin
    w = linucb.user_vector(lin.Minv, lin.b)
    k_ctx, k_r, k_m = jax.random.split(jax.random.PRNGKey(SEED + 1), 3)
    ctx = ops.contexts_fn(k_ctx, lin.occ, 0)
    swaps = _choose_parity("paper", be, be_r, w, lin.Minv, ctx, lin.occ,
                           hyper.alpha)
    x = ctx[:, 0]
    r = jax.random.bernoulli(k_r, 0.5, (n,)).astype(jnp.float32)
    mask = jax.random.bernoulli(k_m, 0.8, (n,))
    up_p = be.update_inv(lin.Minv, lin.b, x, r, mask)
    up_r = be_r.update_inv(lin.Minv, lin.b, x, r, mask)
    for a, b_ in zip(up_p, up_r):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=3e-5, atol=3e-5)
    adj0 = gb.init_adj()
    adj_p = gb.prune(adj0, w, lin.occ, hyper.gamma)
    with _highest():
        adj_r = gb_r.prune(adj0, w, lin.occ, hyper.gamma)
    edge_flips = _prune_parity(w, lin.occ, hyper.gamma, adj_p, adj_r, n,
                               clustering.cb_width)
    lab_p, lab_r = gb.cc(adj_p), gb_r.cc(adj_p)
    np.testing.assert_array_equal(np.asarray(lab_p), np.asarray(lab_r))

    _line("paper", policy="distclub", users=n, d=d, K=K, epochs=epochs,
          engine=be.kind, compiled=not be.interpret,
          first_call_s=f"{first_s:.2f}",
          steady_epoch_ms=f"{1e3 * steady_s / epochs:.1f}",
          interactions=t_p, reward=r_p, reward_reference=r_r,
          clusters=int(ncl[-1]), clusters_reference=int(ncl_r[-1]),
          choose_near_tie_swaps=swaps, prune_boundary_flips=edge_flips,
          parity="ok", peak_bytes=_peak_bytes())

    # DCCB, one epoch (L interaction rounds + one gossip round)
    _check_kernels("paper", "dccb.run",
                   dccb._run.lower(ops, key, hyper, 1, d, DCCB_L, be),
                   {"choose"}, interpret)
    first_s, _ = _timed(dccb.run, ops, key, hyper, 1, d, DCCB_L, backend=be)
    steady_s, (_, m, _) = _timed(dccb.run, ops, key, hyper, 1, d, DCCB_L,
                                 backend=be)
    with _highest():
        _, (_, m_r, _) = _timed(dccb.run, ops, key, hyper, 1, d, DCCB_L,
                                backend=be_r)
    t_p = int(m.interactions.sum())
    r_p, r_r = float(m.reward.sum()), float(m_r.reward.sum())
    # DCCB scores its first L rounds from lagged all-identity statistics,
    # so every candidate ties up to rounding and the two engines pick
    # different (equally valid) candidates there: only the total reward
    # is compared, within the Bernoulli bound.
    _require(abs(r_p - r_r) <= _reward_bound(t_p),
             f"paper: dccb reward {r_p} vs reference {r_r}")
    _line("paper", policy="dccb", users=n, d=d, K=K, epochs=1, L=DCCB_L,
          engine=be.kind, compiled=not be.interpret,
          first_call_s=f"{first_s:.2f}",
          steady_epoch_ms=f"{1e3 * steady_s:.1f}", interactions=t_p,
          reward=r_p, reward_reference=r_r, parity="ok",
          peak_bytes=_peak_bytes())


def _prune_parity(v, occ, gamma, adj_p, adj_r, n, cb_width):
    """Pruned adjacency bits: equal, or flipped only for pairs whose
    distance sits on the threshold up to f32 rounding of the squared
    distance (|vi|^2 + |vj|^2 - 2 vi.vj cancels).  Returns the flip count."""
    diff = np.asarray(adj_p) ^ np.asarray(adj_r)
    rows, words = np.nonzero(diff)
    if not rows.size:
        return 0
    bits = np.unpackbits(diff[rows, words].view(np.uint8).reshape(-1, 4),
                         axis=1, bitorder="little")
    r_idx, b_idx = np.nonzero(bits)
    i = rows[r_idx]
    j = words[r_idx] * 32 + b_idx
    _require((j < n).all(), "paper: prune set a padding bit")
    v = np.asarray(v, np.float64)
    cb = np.asarray(cb_width(occ), np.float64)
    d2 = np.sum((v[i] - v[j]) ** 2, axis=1)
    th = gamma * (cb[i] + cb[j])
    scale = np.sum(v[i] ** 2, 1) + np.sum(v[j] ** 2, 1) + th ** 2
    _require((np.abs(d2 - th ** 2) <= 1e-5 * np.maximum(scale, 1e-12)).all(),
             "paper: prune differs from the reference away from the "
             "threshold")
    return int(i.size)


# ---------------------------------------------------------------------------
# phase: catalog serving
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ServeWorld:
    """Seeded serving deployment: hidden user preferences, a
    region-structured catalog, the hot users that traffic comes from, and
    the reward function."""
    theta: object
    segment: object
    emb: object
    hot: object
    reward_fn: object


def make_world(n_users=N_USERS, d=D, n_items=N_ITEMS, batch=BATCH):
    import jax
    import jax.numpy as jnp
    from repro.core import env

    cenv, segment = env.make_catalog_env(
        jax.random.PRNGKey(SEED), n_users, d, USER_SEGMENTS, n_items,
        n_regions=ITEM_REGIONS, item_noise_scale=0.05)
    theta = cenv.theta

    def reward_fn(key, uids, ctx, slot):
        return env.step_rewards(key, theta[uids], ctx, slot)

    # batch / USER_SEGMENTS random users of each segment, so a batch
    # grouped by segment fills whole kernel user blocks with one segment
    per = batch // USER_SEGMENTS
    order = jnp.lexsort((jax.random.uniform(jax.random.PRNGKey(SEED + 2),
                                            (n_users,)), segment))
    starts = jnp.searchsorted(segment[order], jnp.arange(USER_SEGMENTS))
    hot = order[(starts[:, None] + jnp.arange(per)[None, :]).reshape(-1)]
    return ServeWorld(theta=theta, segment=segment,
                      emb=env.catalog_embeddings(cenv), hot=hot,
                      reward_fn=reward_fn)


def _batch(world, t):
    """Transaction ``t``'s request batch: the hot users in a fresh order,
    grouped by user segment (a segment-aware batch former), so a kernel
    user block shares its item regions."""
    import jax
    import jax.numpy as jnp
    perm = jax.random.permutation(jax.random.PRNGKey(1000 + t), world.hot)
    order = jnp.argsort(world.segment[perm], stable=True)
    return perm[order].astype(jnp.int32)


def _hyper(k_short):
    from repro.configs import distclub_paper as paper
    return paper.CONFIG._replace(n_candidates=k_short)


def _session(n_users, d, k_short, interpret, **kw):
    from repro import serve
    return serve.OnlineBandit.create(
        n_users, d, _hyper(k_short), policy="distclub",
        refresh_every=1 << 30, backend="pallas", interpret=interpret, **kw)


def serve_phase(*, n_users=N_USERS, d=D, k_short=K_SHORT, batch=BATCH,
                n_items=N_ITEMS, warm=6, interpret=False):
    import jax
    import jax.numpy as jnp
    from repro import serve
    from repro.core.backend import BackendConfig
    from repro.serve import session as sess_mod

    world = make_world(n_users, d, n_items, batch)
    cat = serve.make_catalog(world.emb)
    sess = _session(n_users, d, k_short, interpret,
                    pending_capacity=max(4096, batch))
    alpha = sess.policy.cfg.hyper.alpha
    rb = sess_mod._retrieval_engine(sess, k_short)
    _check_engine("serve", sess.policy.cfg.engine, interpret)
    _check_engine("serve", rb, interpret)
    policy, rf = sess.policy, world.reward_fn
    key0, u0 = jax.random.PRNGKey(SEED), _batch(world, 0)
    _check_kernels("serve", "step_catalog",
                   sess_mod._catalog_step_fn(policy, rb, rf, None, (), False)
                   .lower(sess.state, key0, u0, cat),
                   {"topk", "choose", "rank1_update_inv", "graph_prune",
                    "cc_hop"}, interpret)

    # unpruned transactions
    times = []
    for t in range(warm):
        dt, (sess, items, m) = _timed(serve.step_catalog, sess,
                                      jax.random.PRNGKey(t), _batch(world, t),
                                      cat, rf, k_short=k_short)
        times.append(dt)
        _require(bool((np.asarray(items) >= 0).all()),
                 "serve: a valid request got no item")
    unpruned_first, unpruned_ms = times[0], 1e3 * np.median(times[1:])

    # pallas vs reference on this state: shortlist and choose
    uids = _batch(world, warm)
    w, minv, occ = jax.jit(policy.gather_score)(sess.state, uids)
    bank = cat.serving
    rb_r = BackendConfig.create("reference").retrieval(
        d, k_short, row_block=batch, item_block=4096)
    sl_swaps = _shortlist_parity("serve", rb, rb_r, w, minv, occ, bank.emb,
                                 bank.live, alpha)
    _, ids = rb.shortlist(w, minv, occ, bank.emb, bank.live, alpha)
    ctx = bank.emb[jnp.maximum(ids, 0)]
    be = policy.cfg.engine.with_users(batch).with_candidates(k_short)
    be_r = BackendConfig.create("reference").interact(batch, d, k_short)
    ch_swaps = _choose_parity("serve", be, be_r, w, minv, ctx, occ, alpha)

    # cluster-pruned transactions: bit-equal to unpruned, tiles skipped
    clusters = serve.build_clusters(cat)
    _check_kernels("serve", "pruned step_catalog",
                   sess_mod._catalog_step_fn(policy, rb, rf, None, (), True)
                   .lower(sess.state, key0, u0, cat, clusters),
                   {"topk_pruned", "topk", "choose", "rank1_update_inv"},
                   interpret)
    sync = dataclasses.replace(sess, pending=None)
    it_u, _, ctx_u = serve.recommend_catalog(sync, uids, cat,
                                             k_short=k_short)
    it_p, _, ctx_p, rmet = serve.recommend_catalog(
        sync, uids, cat, k_short=k_short, clusters=clusters)
    np.testing.assert_array_equal(np.asarray(it_u), np.asarray(it_p))
    np.testing.assert_array_equal(np.asarray(ctx_u), np.asarray(ctx_p))
    times, skips = [], []
    for t in range(warm, warm + 3):
        dt, (sess, items, m, rmet) = _timed(
            serve.step_catalog, sess, jax.random.PRNGKey(t),
            _batch(world, t), cat, rf, k_short=k_short, clusters=clusters)
        times.append(dt)
        _require(int(rmet.pruned_active) == 1, "serve: pruning not active")
        skips.append(float(rmet.skip_ratio()))
    _require(max(skips) > 0.0, "serve: pruned transactions skipped no tile")
    pruned_first, pruned_ms = times[0], 1e3 * np.median(times[1:])

    # issue -> delayed feedback through the pending ring
    t = warm + 3
    before = serve.pending_stats(sess)
    sess, items, dids, slots, ctx = serve.recommend_catalog(
        sess, _batch(world, t), cat, k_short=k_short)
    realized, _, _, _ = rf(jax.random.PRNGKey(t), _batch(world, t), ctx,
                           slots)
    sess = serve.observe_delayed(sess, dids, realized, catalog=cat)
    after = serve.pending_stats(sess)
    _require(after["matched"] - before["matched"] == batch
             and after["in_flight"] == 0,
             f"serve: pending round trip {before} -> {after}")

    # staged churn + publish: clusters go stale -> unpruned fallback
    churn = batch
    gone = jax.random.choice(jax.random.PRNGKey(SEED + 3), n_items, (churn,),
                             replace=False).astype(jnp.int32)
    cat, n_ret = serve.retire_items(cat, gone)
    cat, slots_new, n_add = serve.add_items(cat, world.emb[gone[::-1]])
    cat = serve.publish(cat)
    _require(int(n_ret) == churn and int(n_add) == churn
             and int(cat.epoch) == 1, "serve: churn publish")
    sess, items, m, rmet = serve.step_catalog(
        sess, jax.random.PRNGKey(t + 1), _batch(world, t + 1), cat, rf,
        k_short=k_short, clusters=clusters)
    _require(int(rmet.pruned_active) == 0,
             "serve: a stale cluster table was trusted after publish")

    # forced stage-2 refresh over all users
    _check_kernels("serve", "refresh",
                   sess_mod._force_refresh_fn(policy, None, ())
                   .lower(sess.state, key0), {"graph_prune", "cc_hop"},
                   interpret)
    refresh_s, sess = _timed(serve.refresh, sess)
    labels = np.asarray(sess.state.labels)
    _require(labels.shape == (n_users,) and labels.min() >= 0,
             "serve: refresh labels")

    _line("serve", users=n_users, d=d, items=n_items, k_short=k_short,
          batch=batch, engine=rb.kind, compiled=not rb.interpret,
          first_call_s=f"{unpruned_first:.2f}",
          steady_step_ms=f"{unpruned_ms:.1f}",
          pruned_first_call_s=f"{pruned_first:.2f}",
          pruned_step_ms=f"{pruned_ms:.1f}",
          skip_ratio=f"{max(skips):.4f}",
          refresh_s=f"{refresh_s:.2f}",
          clusters=int(np.unique(labels).size),
          shortlist_near_tie_swaps=sl_swaps, choose_near_tie_swaps=ch_swaps,
          parity="ok", peak_bytes=_peak_bytes())


# ---------------------------------------------------------------------------
# phase: item-sharded serving on four chips vs one chip
# ---------------------------------------------------------------------------


def four_chip_phase(*, n_users=N_USERS, d=D, k_short=K_SHORT, batch=BATCH,
                    n_items=N_ITEMS, warm=2, interpret=False):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro import serve
    from repro.core import catalog as catalog_mod
    from repro.distributed.distclub_shard import named_shardings
    from repro.launch.mesh import make_mesh

    devices = jax.devices()
    _require(len(devices) == 4, f"--four-chips needs 4 devices, "
                                f"found {len(devices)}")
    axes = ("users",)
    mesh = make_mesh((4,), axes)
    world = make_world(n_users, d, n_items, batch)
    cat1 = jax.device_put(serve.make_catalog(world.emb), devices[0])
    cat4 = jax.device_put(cat1, named_shardings(mesh,
                                                catalog_mod.specs(axes)))
    s1 = _session(n_users, d, k_short, interpret)
    s4 = serve.OnlineBandit.sharded(
        mesh, n_users, d, _hyper(k_short), axes=axes, policy="distclub",
        refresh_every=1 << 30, backend="pallas", interpret=interpret)
    _check_engine("four-chips", s4.policy.cfg.engine, interpret)
    rf = world.reward_fn

    def both(t, cat_1, cat_4, cl_1=None, cl_4=None):
        key, u = jax.random.PRNGKey(t), _batch(world, t)
        o1 = serve.step_catalog(s1, key, u, cat_1, rf, k_short=k_short,
                                clusters=cl_1)
        dt, o4 = _timed(serve.step_catalog, s4, key, u, cat_4, rf,
                        k_short=k_short, clusters=cl_4)
        np.testing.assert_array_equal(np.asarray(o1[1]), np.asarray(o4[1]))
        return o1[0], o4[0], dt, o4

    times = []
    for t in range(warm):
        s1, s4, dt, _ = both(t, cat1, cat4)
        times.append(dt)
    # the cluster tables are replicated on a sharded session
    cl1 = serve.build_clusters(cat1)
    cl4 = jax.device_put(cl1, NamedSharding(mesh, P()))
    skips = []
    for t in range(warm, warm + 2):
        s1, s4, dt, o4 = both(t, cat1, cat4, cl1, cl4)
        _require(int(o4[3].pruned_active) == 1,
                 "four-chips: pruning not active")
        skips.append(float(o4[3].skip_ratio()))
    gone = jax.random.choice(jax.random.PRNGKey(SEED + 3), n_items, (batch,),
                             replace=False).astype(jnp.int32)
    new = world.emb[gone[::-1]]
    cats = []
    for cat in (cat1, cat4):
        cat, _ = serve.retire_items(cat, gone)
        cat, _, _ = serve.add_items(cat, new)
        cats.append(serve.publish(cat))
    cat1, cat4 = cats
    np.testing.assert_array_equal(np.asarray(cat1.serving.emb),
                                  np.asarray(cat4.serving.emb))
    s1, s4, _, _ = both(warm + 2, cat1, cat4)
    for name in ("Minv", "b", "occ", "uMcinv", "ubc"):
        np.testing.assert_array_equal(np.asarray(getattr(s1.state, name)),
                                      np.asarray(getattr(s4.state, name)),
                                      err_msg=f"four-chips: state.{name}")
    _line("four-chips", users=n_users, d=d, items=n_items, k_short=k_short,
          batch=batch, shards=4, engine=s4.policy.cfg.engine.kind,
          compiled=not s4.policy.cfg.engine.interpret,
          first_call_s=f"{times[0]:.2f}",
          steady_step_ms=f"{1e3 * np.median(times[1:] or times):.1f}",
          skip_ratio=f"{max(skips):.4f}", parity="bit-equal",
          peak_bytes=_peak_bytes())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the item-sharded serving phase on a "
                         "4-chip mesh, compared with one chip")
    args = ap.parse_args(argv)

    import jax
    if jax.default_backend() != "tpu":
        print(f"chip_smoke: JAX found no TPU (backend "
              f"{jax.default_backend()!r}); nothing was run",
              file=sys.stderr)
        return 2
    from repro.launch import compile_cache
    compile_cache.enable()

    if args.four_chips:
        four_chip_phase()
    else:
        paper_phase()
        serve_phase()
    dev = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        sys.exit(1)
