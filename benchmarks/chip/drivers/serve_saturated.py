"""Saturated catalog serving: full request batches back to back through
``serve.step_catalog`` (cluster-pruned), the transaction's stage-2 refresh
firing inside it on the interaction budget.  A front end whose queue
never empties.

Set-up (counted in ``setup_s``): the catalog and its item-cluster table,
every user's activity and warm history (``world_movielens``), all made on
the device; one warm-up transaction that fires the refresh and one that
does not, then the refresh budget is set back to zero so that the
window's refreshes close its cycles; the copies the window holds are
made once.  The deployment (its geometry, each user's activity and the
warm history) is drawn from the configuration's fixed ``world_seed``;
``--seed`` draws the traffic: which users arrive in each batch, every
click of the window, and which plain transaction the check holds.

Each transaction donates the session state (``step_catalog(...,
donate=True)``): the state is updated in place, and the host can queue
the next transaction while the device runs the last.

Window: refresh cycles of ``refresh_every / batch`` full transactions,
the last of which refreshes.  Users are drawn with probability
proportional to their activity, and each batch is ordered by cohort, as
``traffic/generator.form_batch`` does.  A cycle is dispatched without a
host wait (the next cycle's batches are drawn while the device runs it),
and the host blocks at its end.  Cycles run until ``--seconds`` have
passed at a cycle's end; ``interactions_per_s`` is the interactions of
those whole cycles over the time from the window's start to the end of
the last, so a window's edge cannot move the rate by a cycle.

Held for the check: a whole state at 162,541 users is 3.8 GB, so only
what the comparison reads is kept, and only from the cycle in flight:
copies of the user statistics before and after one plain transaction
(its position in the cycle drawn from the seed) and before the cycle's
refresh-firing transaction, with a copy of that transaction's graph
before it, each made before the state is donated.  The last cycle's
refresh is the one compared, so its after-state is the live state.
``open_loop.check_tx`` compares each held transaction with the plain
reference; the refresh's graph and labels are compared with
``reference/serve_blocked.py``, which takes a user count that no block
divides.
"""
from __future__ import annotations

import dataclasses
import functools
import sys
import time
from typing import NamedTuple

import numpy as np

from .. import world as W
from .. import world_movielens as ML
from ..reference import serve as ref
from ..reference import serve_blocked as blocked
from ..traffic import generator as gen
from . import open_loop


class Rows(NamedTuple):
    """The user statistics ``open_loop.check_tx`` reads from a state."""
    Minv: object
    b: object
    occ: object
    uMcinv: object
    ubc: object
    umean_occ: object


class Graph(NamedTuple):
    """A graph before a refresh (``check_refresh`` reads its ``adj``)."""
    adj: object


def _rows(state):
    """A copy of the rows ``check_tx`` reads, made before ``state`` is
    donated."""
    import jax.numpy as jnp
    return Rows(*(jnp.copy(getattr(state, k)) for k in Rows._fields))


def _step(cell, uids, tx):
    """One donating transaction; ``tx`` counts from the seed's ``tx0``."""
    from repro import serve
    return serve.step_catalog(cell.session, W.tx_key(cell.word,
                                                      cell.tx0 + tx),
                              uids, cell.catalog, cell.reward_fn,
                              k_short=cell.cfg["k_short"],
                              clusters=cell.clusters, donate=True)


@dataclasses.dataclass
class Cell:
    cfg: dict
    traffic: dict
    seed: int
    word: int                       # the world word (``world_seed``)
    tx0: int                        # the seed's first transaction key
    session: object
    catalog: object
    clusters: object
    emb: object
    reward_fn: object
    cohort: np.ndarray
    p_user: np.ndarray              # draw probability of each user
    rng: np.random.Generator


def _draw(cell, count):
    """``count`` full batches of users drawn in proportion to activity,
    each ordered by cohort."""
    B = cell.cfg["batch"]
    users = cell.rng.choice(len(cell.p_user), size=(count, B),
                            p=cell.p_user).astype(np.int32)
    return [gen.form_batch(np.arange(B), u, cell.cohort, B)[0]
            for u in users]


def setup(cfg, traffic, seed, *, interpret=False):
    import jax
    import jax.numpy as jnp
    from repro import serve

    t0 = time.perf_counter()
    n, d, N = cfg["n_users"], cfg["d"], cfg["n_items"]
    cohorts, regions = cfg["user_cohorts"], cfg["item_regions"]
    word = W.world_word(cfg["world_seed"])
    emb = jax.jit(functools.partial(
        W.catalog_embeddings, n_items=N, d=d, regions=regions,
        item_noise=cfg["item_noise"]))(np.uint32(word))
    catalog = jax.jit(functools.partial(
        serve.make_catalog, capacity=cfg["capacity"],
        precision=cfg["precision"]))(emb)
    clusters = jax.jit(functools.partial(
        serve.build_clusters, tile_items=cfg["tile_items"],
        gamma=cfg["item_cluster_gamma"], kind="pallas",
        interpret=interpret))(catalog)
    act = ML.activities(cfg["world_seed"], n_users=n,
                        total=cfg["n_ratings"], floor=cfg["min_ratings"],
                        sigma=cfg["activity_sigma"])
    Minv, b, occ = ML.warm_history(
        word, emb, act, d=d, cohorts=cohorts, regions=regions,
        top=cfg["history_top_regions"],
        share=cfg["history_in_region_share"])
    sess = serve.OnlineBandit.create(
        n, d, open_loop._hyper(cfg), policy="distclub",
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
    cell = Cell(cfg=cfg, traffic=traffic, seed=seed, word=word,
                tx0=int(gen.rng(seed, 7).integers(0, 2**31)),
                session=dataclasses.replace(sess, state=st),
                catalog=catalog, clusters=clusters, emb=emb,
                reward_fn=W.make_reward_fn(d=d, cohorts=cohorts),
                cohort=np.arange(n) % cohorts, p_user=act / act.sum(),
                rng=gen.rng(seed, 3))
    del sess, st, Minv, b, occ
    jax.block_until_ready(cell.session.state)
    t_world = time.perf_counter() - t0
    for t, uids in enumerate(_draw(cell, 2)):
        sess, items, _, _ = _step(cell, uids, 2**31 + t)
        jax.block_until_ready(items)
        cell.session = sess
    cell.session = dataclasses.replace(cell.session, state=(
        cell.session.state._replace(
            since_refresh=jnp.zeros((), jnp.int32))))
    # the copies the window holds compile here
    jax.block_until_ready((_rows(cell.session.state),
                           jnp.copy(cell.session.state.adj)))
    print(f"serve_saturated set-up: world {t_world:.2f} s, warm-up "
          f"transactions {time.perf_counter() - t0 - t_world:.2f} s",
          file=sys.stderr)
    return cell


@dataclasses.dataclass
class Window:
    items: list                 # device [batch] per transaction
    rmets: list                 # device RetrievalMetrics per transaction
    cycles: int
    done_s: float
    held: dict                  # the last cycle's held transactions


def window(cell, seconds, span=None):
    import jax
    import jax.numpy as jnp
    span = span or gen._no_span
    per = cell.cfg["refresh_every"] // cell.cfg["batch"]
    pick = int(gen.rng(cell.seed, 5).integers(0, per - 1))
    out = Window(items=[], rmets=[], cycles=0, done_s=0.0, held={})
    batches = _draw(cell, per)
    tx = 0
    t0 = time.perf_counter()
    while True:
        out.held = held = {}        # only the cycle in flight is held
        with span("bench.dispatch"):
            for k, uids in enumerate(batches):
                state = cell.session.state
                keep = {"tx": cell.tx0 + tx, "index": tx, "uids": uids}
                if k == pick:
                    held["plain"] = dict(keep, before=_rows(state))
                if k == per - 1:
                    held["refresh"] = dict(keep, before=_rows(state),
                                           graph=Graph(jnp.copy(state.adj)))
                del state
                sess, items, _, rmet = _step(cell, uids, tx)
                if k == pick:
                    held["plain"]["after"] = _rows(sess.state)
                if k == per - 1:
                    held["refresh"]["after"] = sess.state
                cell.session = sess
                out.items.append(items)
                out.rmets.append(rmet)
                tx += 1
        with span("bench.draw"):
            batches = _draw(cell, per)
        with span("bench.wait"):
            jax.block_until_ready(items)
        out.done_s = time.perf_counter() - t0
        out.cycles += 1
        if out.done_s >= seconds:
            return out


def results(cell, win):
    """End-to-end metrics and counters of the window (after it closed)."""
    import jax
    cfg = cell.cfg
    items = np.concatenate(jax.device_get(win.items))
    failed = int(np.sum((items < 0) | (items >= cfg["n_items"])))
    inter = len(items)
    # a program without the fold-pass count (an older one) leaves it out
    rm = {k: int(np.sum(jax.device_get([getattr(r, k) for r in win.rmets])))
          for k in ("tiles_skipped", "tiles_total", "pruned_active",
                    "fold_passes") if hasattr(win.rmets[0], k)}
    T = cfg["capacity"] // cfg["tile_items"]
    blocks = rm["tiles_total"] // (T * len(win.items))
    counters = {
        "interactions": inter, "transactions": len(win.items),
        "cycles": win.cycles, "refreshes": win.cycles,
        "tiles_skipped": rm["tiles_skipped"],
        "tiles_total": rm["tiles_total"],
        "pruned_inactive": len(win.rmets) - rm["pruned_active"],
        # the sizes of one (user block, tile) visit of the pruned stream
        "user_blocks_per_tx": blocks,
        "block_users": cfg["batch"] // max(blocks, 1),
        "tile_items": cfg["tile_items"], "d": cfg["d"],
        "window_s": float(win.done_s),
    }
    if "fold_passes" in rm:
        counters["fold_passes"] = rm["fold_passes"]
    e2e = {"interactions_per_s": float(inter / win.done_s)}
    return e2e, counters, inter, failed


def check_refresh(cell, held, passes=6):
    """``prune_margin``, ``prune_flips`` and ``cc_mismatch`` of the held
    refresh, as ``open_loop.check_refresh`` reads them."""
    b1 = held["after"]
    gamma = cell.cfg["gamma"]
    count, pairs = blocked.prune_flips(b1.Minv, b1.b, b1.occ, gamma,
                                       held["graph"].adj, b1.adj,
                                       passes=passes)
    margin = ref.prune_margin(pairs, b1.Minv, b1.b, b1.occ, gamma)
    labels = blocked.components(b1.adj, cell.cfg["n_users"])
    mismatch = int(np.sum(labels != np.asarray(b1.labels)))
    return {"prune_margin": margin, "prune_flips": count,
            "cc_mismatch": mismatch}


def check(cell, win, passes=6):
    """All numbers of the run, for ``correct``; the window's own held
    transactions must exist."""
    if not win.held:
        return {"held_transactions": 0}
    out = {}
    for name in ("plain", "refresh"):
        h = win.held[name]
        got = open_loop.check_tx(cell, h,
                                 np.asarray(win.items[h["index"]]), passes)
        for key, val in got.items():
            out[key] = max(out.get(key, 0), val)
    out.update(check_refresh(cell, win.held["refresh"], passes))
    return out


def control(cell, win, passes=3):
    """The control's numbers: the reference at ``passes`` in the program's
    place on the same held inputs."""
    out = {}
    for name in ("plain", "refresh"):
        got = open_loop.check_tx(cell, win.held[name], None, passes,
                                 control=True)
        for key in ("item_gap", "fold_rel_err"):
            out[key] = max(out.get(key, 0), got[key])
    held = win.held["refresh"]
    b1 = held["after"]
    gamma = cell.cfg["gamma"]
    count, pairs = blocked.control_flips(b1.Minv, b1.b, b1.occ, gamma,
                                         held["graph"].adj)
    out["prune_margin"] = ref.prune_margin(pairs, b1.Minv, b1.b, b1.occ,
                                           gamma)
    out["prune_flips"] = count
    return out


def release(cell, win):
    """Drop the program's live state (the held transactions stay)."""
    cell.session = None
    cell.catalog = None
    cell.clusters = None
