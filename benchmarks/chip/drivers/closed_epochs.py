"""Closed-loop DistCLUB epochs on the paper's synthetic set: back-to-back
``distclub.run`` calls of ``epochs_per_call`` full four-stage epochs from
a fresh state, each with its own key from ``--seed``.

Set-up: the environment and the engines, and two calls (the first
compiles, or reads the program from the persistent cache).  Window: calls
until ``--seconds`` have passed; ``interactions_per_s`` is the
interactions of every call completed, over the time from the window's
start to the last completion.  The check replays one seed-drawn call of
the window with the plain reference.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

from .. import world as W
from ..reference import distclub as ref
from ..traffic import generator as gen


@dataclasses.dataclass
class Cell:
    cfg: dict
    traffic: dict
    seed: int
    ops: object
    env: tuple
    be: object
    gb: object
    hyper: object
    keys: np.ndarray


def _key(seed, c):
    import jax
    return jax.random.fold_in(jax.random.PRNGKey(W.world_word(seed)), c)


def setup(cfg, traffic, seed, *, interpret=False):
    import jax
    from repro.core.backend import BackendConfig
    from repro.core.env_ops import EnvOps
    from repro.core.types import BanditHyper

    n, d, K = cfg["n_users"], cfg["d"], cfg["n_candidates"]
    theta = W.paper_theta(cfg["world_seed"], n_users=n, d=d,
                          n_clusters=cfg["n_clusters"])
    env = W.paper_env_fns(theta, K)
    ops = EnvOps(env[0], env[1], n, d, K)
    hyper = BanditHyper(alpha=cfg["alpha"], beta=cfg["beta"],
                        gamma=cfg["gamma"], sigma=cfg["sigma"],
                        n_candidates=K, max_rounds=cfg["max_rounds"])
    bc = BackendConfig.create("pallas", cfg["precision"])
    be = bc.interact(n, d, K, interpret=interpret)
    gb = bc.graph(n, interpret=interpret)
    for eng in (be, gb):
        if eng.kind != "pallas" or eng.interpret != interpret:
            raise RuntimeError(f"engine resolved to kind={eng.kind!r} "
                               f"interpret={eng.interpret}")
    keys = np.stack([np.asarray(_key(seed, c)) for c in range(64)])
    cell = Cell(cfg=cfg, traffic=traffic, seed=seed, ops=ops, env=env,
                be=be, gb=gb, hyper=hyper, keys=keys)
    for c in (62, 63):                       # warm-up keys, not the window's
        jax.block_until_ready(_call(cell, c))
    return cell


def _call(cell, c):
    from repro.core import distclub
    return distclub.run(cell.ops, cell.keys[c], cell.hyper,
                        cell.traffic["epochs_per_call"], cell.cfg["d"],
                        backend=cell.be, graph=cell.gb)


@dataclasses.dataclass
class Window:
    outputs: list
    done_s: float
    held: dict


def window(cell, seconds, span=None):
    import jax
    span = span or gen._no_span
    out, held = [], {}
    pick = int(gen.rng(cell.seed, 5).integers(0, 3))
    t0 = time.perf_counter()
    c = 0
    while True:
        with span("bench.dispatch"):
            st, m, _ = _call(cell, c)
        with span("bench.wait"):
            jax.block_until_ready(m)
        done = time.perf_counter() - t0
        out.append(m.interactions)
        if c == pick:
            held = {"call": c, "state": st, "metrics": m}
        c += 1
        if done >= seconds or c >= 60:
            break
    if not held:
        held = {"call": c - 1, "state": st, "metrics": m}
    return Window(outputs=out, done_s=done, held=held)


def results(cell, win):
    inter = [int(np.asarray(x).sum()) for x in win.outputs]
    e2e = {"interactions_per_s": float(sum(inter) / win.done_s)}
    counters = {"calls": len(inter),
                "epochs": len(inter) * cell.traffic["epochs_per_call"],
                "interactions": int(sum(inter)),
                "window_s": float(win.done_s)}
    return e2e, counters, len(inter), 0


def _program(st, m):
    """The program's outputs in the reference's terms."""
    return ref.Replay(Minv=st.lin.Minv, b=st.lin.b, occ=st.lin.occ,
                      reward=m.reward, adj=st.graph.adj,
                      labels=st.graph.labels, size=st.clusters.size,
                      seen=st.clusters.seen, s2_Minv=None, s2_b=None,
                      s2_occ=None)


def _compare(cell, rep, side, s2, known):
    """The numbers of one run ``side`` against the reference's ``rep``.

    ``occ_mismatch_share``: users whose interaction count differs (every
    user's budgets are the same function of the configuration here, so a
    sound run matches exactly).  ``stage1_gap_per_kuser``: the summed
    per-round reward gap over the first stage of the first epoch, per
    thousand users; there no user's choices depend on another's, so a
    sound run differs only by a rare near-tie pick.  ``prune_margin``: the
    final graph's bits against the float64 prune of the stage-2
    statistics ``s2`` it was built from, over pairs of ``known`` users
    (``reference.distclub.prune_check``).  ``cc_mismatch``: users whose
    label is not the smallest id of their component in the final graph.
    ``cluster_size_mismatch``: labels whose member count or summed
    interaction count differs from the final labels'.

    Later rounds couple users through the clustering and amplify any
    near-tie into a different but valid trajectory, so ``diverged_share``
    (users whose final ``b`` or ``occ`` differ) and the whole run's
    ``reward_gap_sigma`` are reported and not compared."""
    n = cell.cfg["n_users"]
    occ_p, occ_r = np.asarray(side.occ), np.asarray(rep.occ)
    R_p = np.asarray(side.reward, np.float64).reshape(-1)
    R_r = np.asarray(rep.reward, np.float64).reshape(-1)
    t = max(1, int(occ_r.sum()))
    s1 = cell.cfg["max_rounds"]
    d1 = np.abs(R_p[:s1] - R_r[:s1])
    finite = bool(np.isfinite(np.asarray(side.Minv)).all())
    flips, margin = ref.prune_check(side.adj, s2.s2_Minv, s2.s2_b,
                                    s2.s2_occ, cell.cfg["gamma"], known)
    labels = np.asarray(side.labels)
    cc = int(np.sum(ref.components(side.adj, n) != labels))
    size = np.bincount(labels, minlength=n)
    seen = np.bincount(labels, weights=np.asarray(s2.s2_occ[-1]),
                       minlength=n)
    stats = int(np.sum((np.asarray(side.size) != size)
                       | (np.asarray(side.seen) != seen)))
    return {"occ_mismatch_share": float(np.mean(occ_p != occ_r))
            if finite else np.inf,
            "stage1_gap_per_kuser": float(d1.sum()) * 1e3 / n,
            "prune_margin": margin,
            "cc_mismatch": cc,
            "cluster_size_mismatch": stats,
            "stage1_reward_maxdiff": float(d1.max()),
            "prune_flips": flips,
            "known_share": float(np.mean(known)),
            "diverged_share": float(1.0 - np.mean(_agree(side, rep))),
            "reward_gap_sigma": abs(float(R_p.sum() - R_r.sum()))
            / np.sqrt(t)}


def _agree(side, rep):
    """Users whose final ``occ``, ``b`` and ``Minv`` match the reference's:
    their whole trajectory did, so their stage-2 statistics were the
    reference's too.  ``b`` alone is not enough: a pick that differs and
    is unrewarded on both sides leaves ``b`` equal and moves ``Minv``."""
    b_p = np.asarray(side.b, np.float64)
    b_r = np.asarray(rep.b, np.float64)
    scale = np.maximum(1.0, np.abs(b_r).max(axis=1))
    return ((np.asarray(side.occ) == np.asarray(rep.occ))
            & (np.abs(b_p - b_r).max(axis=1) <= 1e-3 * scale)
            & (minv_rel_err(side, rep) <= 1e-3))


def minv_rel_err(side, rep):
    """Per user, the largest gap of the final ``Minv`` entries over the
    largest entry of the reference's (a diagonal one, at most 1)."""
    M_p = np.asarray(side.Minv, np.float32)
    M_r = np.asarray(rep.Minv, np.float32)
    gap = np.abs(M_p - M_r).max(axis=(1, 2))
    return gap / np.maximum(np.abs(M_r).max(axis=(1, 2)), 1e-30)


def _replay(cell, call, passes):
    cfg = cell.cfg
    hyper = {k: cfg[k] for k in ("alpha", "beta", "gamma", "sigma",
                                 "max_rounds")}
    return ref.run(cell.keys[call], cell.env[0], cell.env[1], cfg["n_users"],
                   hyper, cell.traffic["epochs_per_call"], cfg["d"],
                   passes=passes)


def check(cell, win):
    """The held call against the reference's replay of its key.  Pairs
    are judged where the program's stage-2 statistics are known: between
    users whose whole trajectory matched the replay's."""
    held = win.held
    rep = _replay(cell, held["call"], 6)
    side = _program(held["state"], held["metrics"])
    return _compare(cell, rep, side, rep, _agree(side, rep))


def control(cell, win, passes=3):
    """The reference at ``passes`` in the program's place, compared with
    the reference at full precision; its own stage-2 statistics are known
    for every user."""
    call = win.held["call"]
    rep = _replay(cell, call, 6)
    low = _replay(cell, call, passes)
    return _compare(cell, rep, low, low,
                    np.ones(cell.cfg["n_users"], bool))


def release(cell, win):
    pass
