"""Open-loop catalog serving: ``serve.step_catalog`` (cluster-pruned) under
a seeded arrival schedule, the transaction's refresh firing inside it on
the interaction budget.

Set-up (counted in ``setup_s``): the seeded world and catalog, the warm
user history and the item-cluster table, all made on the device; one
warm-up transaction that fires the refresh (so both branches of the
transaction program run before the window) and one that does not.

Window: the greedy batch former of ``traffic/generator.py``; each
request is timed from its scheduled arrival to the ``block_until_ready``
of the transaction that served it, and the queue is drained after the
window.  The check holds on to the state before and after one sampled
refresh-firing transaction and the plain transaction before it (drawn
from the seed), and compares them with the plain reference once the
window has closed.
"""
from __future__ import annotations

import dataclasses
import functools

import numpy as np

from .. import world as W
from ..reference import serve as ref
from ..traffic import generator as gen


@dataclasses.dataclass
class Cell:
    cfg: dict
    traffic: dict
    seed: int
    word: int
    session: object
    catalog: object
    clusters: object
    emb: object
    reward_fn: object
    cohort: np.ndarray
    since: int                      # host copy of the refresh budget


def _hyper(cfg):
    from repro.core.types import BanditHyper
    return BanditHyper(alpha=cfg["alpha"], beta=cfg["beta"],
                       gamma=cfg["gamma"], sigma=cfg["sigma"],
                       n_candidates=cfg["k_short"],
                       max_rounds=cfg["max_rounds"])


def _step(cell, uids, tx):
    from repro import serve
    return serve.step_catalog(cell.session, W.tx_key(cell.word, tx), uids,
                              cell.catalog, cell.reward_fn,
                              k_short=cell.cfg["k_short"],
                              clusters=cell.clusters)


def setup(cfg, traffic, seed, *, interpret=False):
    import jax
    import jax.numpy as jnp
    from repro import serve

    n, d, N = cfg["n_users"], cfg["d"], cfg["n_items"]
    cohorts = cfg["user_cohorts"]
    word = W.world_word(seed)
    cat_spec = traffic["catalog"]
    emb = jax.jit(functools.partial(
        W.catalog_embeddings, n_items=N, d=d, regions=cat_spec["regions"],
        item_noise=cat_spec["item_noise"]))(np.uint32(word))
    catalog = jax.jit(functools.partial(serve.make_catalog,
                                        precision=cfg["precision"]))(emb)
    clusters = jax.jit(functools.partial(
        serve.build_clusters, tile_items=cfg["tile_items"], kind="pallas",
        interpret=interpret))(catalog)
    Minv, b, occ = jax.jit(functools.partial(
        W.warm_history, n_users=n, d=d, cohorts=cohorts,
        length=cfg["warm_history"]))(np.uint32(word), emb)
    sess = serve.OnlineBandit.create(
        n, d, _hyper(cfg), policy="distclub",
        refresh_every=cfg["refresh_every"], backend="pallas",
        interpret=interpret, precision=cfg["precision"])
    eng = sess.policy.cfg.engine
    if eng.kind != "pallas" or eng.interpret != interpret:
        raise RuntimeError(f"engine resolved to kind={eng.kind!r} "
                           f"interpret={eng.interpret}, want pallas "
                           f"interpret={interpret}")
    # the first warm-up transaction finds its budget spent and refreshes
    st = sess.state._replace(
        Minv=Minv.astype(sess.state.Minv.dtype), b=b, occ=occ,
        since_refresh=jnp.asarray(cfg["refresh_every"], jnp.int32))
    sess = dataclasses.replace(sess, state=st)
    cell = Cell(cfg=cfg, traffic=traffic, seed=seed, word=word,
                session=sess, catalog=catalog, clusters=clusters, emb=emb,
                reward_fn=W.make_reward_fn(d=d, cohorts=cohorts),
                cohort=np.arange(n) % cohorts, since=0)
    warm = gen.rng(seed, 3).integers(0, n, (2, cfg["batch"])).astype(
        np.int32)
    for t, u in enumerate(warm):
        uids, _ = gen.form_batch(np.arange(len(u)), u, cell.cohort,
                                 cfg["batch"])
        sess, items, _, rmet = _step(cell, uids, 2**31 + t)
        jax.block_until_ready(items)
        cell.session = sess
    cell.since = len(warm[-1])
    return cell


@dataclasses.dataclass
class Window:
    loop: gen.OpenLoopResult
    schedule: np.ndarray
    items: list                 # device [batch] per transaction
    rmets: list                 # device RetrievalMetrics per transaction
    valid: list                 # valid requests per transaction
    refreshed: list             # transaction indices that refreshed
    held: dict                  # the sampled transactions' states and inputs


def window(cell, seconds, span=None):
    import jax
    cfg, tr = cell.cfg, cell.traffic
    schedule = gen.arrival_schedule(tr["rate_per_s"], seconds, cell.seed)
    users = gen.draw_users(cfg["n_users"], len(schedule), cell.seed)
    target = 1 + int(gen.rng(cell.seed, 4).integers(0, 2))
    out = Window(loop=None, schedule=schedule, items=[],
                 rmets=[], valid=[], refreshed=[], held={})
    prev = {}                   # the transaction before the current one

    def serve(uids, tx):
        nonlocal prev
        valid = int((uids >= 0).sum())
        fires = cell.since + valid >= cfg["refresh_every"]
        before = cell.session
        with (span or gen._no_span)("bench.dispatch"):
            sess, items, _, rmet = _step(cell, uids, tx)
        with (span or gen._no_span)("bench.wait"):
            jax.block_until_ready(items)
        cell.session = sess
        cell.since = 0 if fires else cell.since + valid
        out.items.append(items)
        out.rmets.append(rmet)
        out.valid.append(valid)
        this = {"tx": tx, "uids": uids, "before": before.state,
                "after": sess.state}
        if fires:
            out.refreshed.append(tx)
            if len(out.refreshed) <= target and prev:
                out.held = {"plain": prev, "refresh": this}
        # once the sampled pair is held, stop holding the previous state
        done = bool(out.held) and len(out.refreshed) >= target
        prev = this if not (fires or done) else {}

    out.loop = gen.run_open_loop(schedule, users, cell.cohort, cfg["batch"],
                                 serve, span=span)
    return out


def results(cell, win):
    """End-to-end metrics and counters of the window (after it closed)."""
    import jax
    items = np.concatenate([np.asarray(i) for i in win.items])
    order = np.concatenate(win.loop.batches)
    per_tx = [len(b) for b in win.loop.batches]
    got = np.full(len(win.schedule), -1, np.int64)
    for k, b in enumerate(win.loop.batches):
        got[b] = items[k * cell.cfg["batch"]:k * cell.cfg["batch"] + len(b)]
    failed = int(np.sum((got < 0) | (got >= cell.cfg["n_items"])))
    rm = jax.device_get([(r.tiles_skipped, r.tiles_total, r.pruned_active)
                         for r in win.rmets])
    lat_ms = win.loop.latency_s * 1e3
    e2e = {
        "req_p50_ms": float(np.percentile(lat_ms, 50)),
        "req_p99_ms": float(np.percentile(lat_ms, 99)),
        "requests_per_s": float((len(order) - failed) / win.loop.done_s),
    }
    counters = {
        "requests": len(order), "transactions": len(per_tx),
        "valid_per_tx": [int(v) for v in win.valid],
        "refreshes": len(win.refreshed),
        "tiles_skipped": int(sum(int(s) for s, _, _ in rm)),
        "tiles_total": int(sum(int(t) for _, t, _ in rm)),
        "pruned_inactive": int(sum(1 for _, _, a in rm if int(a) != 1)),
        "generator_late_s": float(win.loop.sleep_late_s),
        "window_s": float(win.loop.done_s),
    }
    return e2e, counters, len(order), failed


# ---------------------------------------------------------------------------
# the check: what the sampled transactions produced, against the reference
# ---------------------------------------------------------------------------

_ROWS = ("Minv", "b", "occ", "uMcinv", "ubc", "umean_occ")


def _rows(state, uids):
    import jax
    import jax.numpy as jnp
    idx = jnp.asarray(np.clip(uids, 0, None))
    return {k: getattr(state, k)[idx] for k in _ROWS}


def check_tx(cell, held, items, passes=6, control=False):
    """Numbers of one held transaction: ``item_gap``, ``fold_rel_err`` and
    ``rows_touched_outside_batch``.  With ``control`` the served items are
    the reference's own at ``passes`` (the control in the program's place)."""
    import jax
    import jax.numpy as jnp
    cfg = cell.cfg
    uids = held["uids"]
    valid = uids >= 0
    rows = _rows(held["before"], uids)
    w, M = ref.mix(rows, cfg["beta"], passes)
    live = jnp.ones((cfg["n_items"],), jnp.float32)
    _, cand = ref.ucb_top(w, M, rows["occ"], cell.emb, live, cfg["alpha"],
                          passes=passes)
    if control:
        items = cand[:, 0]
    items = np.where(valid, items, -1)
    emb_np = lambda ids: np.asarray(cell.emb[jnp.asarray(ids)], np.float64)
    w64, M64, occ64 = ref.mix64(jax.device_get(rows), cfg["beta"])
    v = np.nonzero(valid)[0]
    gap = ref.item_gap(items[v], cand[v], emb_np, w64[v], M64[v], occ64[v],
                       cfg["alpha"], cfg["n_items"])
    # the click each request drew: the reward function's own draws
    key = W.tx_key(cell.word, held["tx"])
    u = np.asarray(W.click_draws(jnp.asarray(key), len(uids)), np.float64)
    theta = np.asarray(W.user_theta(np.uint32(cell.word), jnp.asarray(uids),
                                    d=cfg["d"], cohorts=cfg["user_cohorts"]),
                       np.float64)
    x = emb_np(np.clip(items, 0, cfg["n_items"] - 1))
    p = 0.5 * (1.0 + np.sum(x * theta, axis=1))
    r = (u < p).astype(np.float64)
    amb = np.abs(u - p) < 1e-5
    if control:
        Mc, bc, oc = ref.fold_rows(rows["Minv"], rows["b"], rows["occ"],
                                   np.where(valid, uids, -1), x, r, passes)
        after_rows = jax.device_get({"Minv": Mc, "b": bc, "occ": oc})
    else:
        after_rows = jax.device_get(_rows(held["after"], uids))
    before_rows = jax.device_get(rows)
    ok = valid & (items >= 0)
    err, seen, skipped = ref.fold_err(np.where(ok, uids, -1), x, r, amb,
                                      before_rows, after_rows)
    if not ok[valid].all():
        err = np.inf
    b0, b1 = held["before"], held["after"]
    changed = ((jnp.any(b0.Minv != b1.Minv, axis=(1, 2)))
               | jnp.any(b0.b != b1.b, axis=1) | (b0.occ != b1.occ))
    outside = changed.at[jnp.asarray(uids[valid])].set(False)
    return {"item_gap": gap, "fold_rel_err": float(err),
            "rows_touched_outside_batch": int(jnp.sum(outside)),
            "fold_users_compared": seen, "fold_users_ambiguous": skipped}


def check_refresh(cell, held, passes=6):
    """``prune_margin`` and ``cc_mismatch`` of the held refresh."""
    b0, b1 = held["before"], held["after"]
    n = cell.cfg["n_users"]
    count, pairs = ref.prune_flips(b1.Minv.astype("float32"), b1.b, b1.occ,
                                   cell.cfg["gamma"], b0.adj, b1.adj,
                                   passes=passes)
    margin = ref.prune_margin(pairs, b1.Minv, b1.b, b1.occ,
                              cell.cfg["gamma"])
    labels = ref.components(b1.adj, n)
    mismatch = int(np.sum(labels != np.asarray(b1.labels)))
    return {"prune_margin": margin, "prune_flips": count,
            "cc_mismatch": mismatch}


def check(cell, win, passes=6):
    """All numbers of the run, for ``correct``; the window's own sampled
    transactions must exist."""
    if not win.held:
        return {"held_transactions": 0}
    held = win.held
    out = {}
    for name in ("plain", "refresh"):
        k = held[name]["tx"]
        got = check_tx(cell, held[name], np.asarray(win.items[k]), passes)
        for key, val in got.items():
            out[key] = max(out.get(key, 0), val)
    out.update(check_refresh(cell, held["refresh"], passes))
    return out


def control(cell, win, passes=3):
    """The control's numbers: the reference at ``passes`` put in the
    program's place on the same held inputs."""
    held = win.held
    out = {}
    for name in ("plain", "refresh"):
        got = check_tx(cell, held[name], None, passes, control=True)
        for key in ("item_gap", "fold_rel_err"):
            out[key] = max(out.get(key, 0), got[key])
    # the control's own prune, compared with the reference's prune
    b0, b1 = held["refresh"]["before"], held["refresh"]["after"]
    ref_adj = _reference_adj(cell, b0, b1, 6)
    count, pairs = ref.prune_flips(b1.Minv, b1.b, b1.occ, cell.cfg["gamma"],
                                   b0.adj, ref_adj, passes=passes)
    out["prune_margin"] = ref.prune_margin(pairs, b1.Minv, b1.b, b1.occ,
                                           cell.cfg["gamma"])
    out["prune_flips"] = count
    return out


def _reference_adj(cell, b0, b1, passes):
    import jax.numpy as jnp
    v = ref.contract("nij,nj->ni", b1.Minv, b1.b, passes)
    sq = jnp.sum(v * v, axis=1)
    cb = ref.cb_width(b1.occ)
    n = v.shape[0]
    out = []
    for r0 in range(0, n, 512):
        dot = ref.contract("id,jd->ij", v[r0:r0 + 512], v, passes)
        d2 = sq[r0:r0 + 512, None] + sq[None, :] - 2.0 * dot
        keep = jnp.sqrt(jnp.maximum(d2, 0.0)) < cell.cfg["gamma"] * (
            cb[r0:r0 + 512, None] + cb[None, :])
        C = b0.adj.shape[1] * 32
        keep = jnp.pad(keep, ((0, 0), (0, C - n)))
        out.append(b0.adj[r0:r0 + 512] & ref._pack(keep))
    return jnp.concatenate(out)


def release(cell, win):
    """Drop the program's live state (the held transactions stay)."""
    cell.session = None
    cell.catalog = None
    cell.clusters = None
