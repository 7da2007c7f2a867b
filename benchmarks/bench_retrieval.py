"""Catalog-scale retrieval benchmark + the streaming-vs-dense HBM model.

Times the streaming UCB top-K shortlist (``RetrievalBackend``) against a
persistent item catalog at serving shapes, and models the HBM traffic
both ways:

  dense       score the whole catalog as one ``[B, N]`` op chain
              (einsum -> [B, N, d] quad intermediate -> scores ->
              top_k), each XLA op streaming its operands.  Per user:
              ``N d / B`` (catalog stream amortized over the request
              block) + ``2 N d`` ([N, d] quad intermediate write+read)
              + ``2 N`` (scores write + top-k read) + ``d^2 + d`` state.
  streaming   the retrieval engine: the catalog streams through VMEM
              once per user block and ONLY the ``[B, K_short]`` shortlist
              is written — no [N, d] intermediate, no score matrix.
              Per user: ``N d / Bu`` + ``d^2 + d`` + ``4 K_short``.

The modeled cut (``hbm_cut_ratio``) is what the two-stage redesign buys
on the item axis — the CI regression gate tracks it (≥8x is the PR-5
acceptance floor at N=262144, d=32, K_short=64; the model gives ~250x).

Wall-clock columns: the reference engine rows are honest CPU numbers
(the row-blocked oracle is also the off-TPU serving path); the pallas
row is interpret-mode off-TPU — kernel-path validation, not a speed
claim (same convention as every other bench, flagged per record).
A ``N_items = 2**20`` reference row demonstrates catalog scale on one
CPU core, and an 8-device item-sharded serving row (subprocess mesh)
runs the full two-stage ``step_catalog`` transaction with the modeled
comm volume: ``O(B K_short S)`` merge words vs ``O(B N)`` for shipping
dense scores.

The ``pruned`` row exercises cluster-pruned retrieval (README
"Cluster-pruned retrieval") on a region-structured catalog: item-side
CLUB clusters + per-tile UCB upper bounds let the stream skip tiles
whose bound cannot beat the running shortlist floor.  Pruning is EXACT
(the row asserts the pruned shortlist bit-equal to unpruned), so the
gated metric is pure savings: ``tiles_skipped_ratio`` (fraction of tile
visits avoided, gate ≥ 0.5 at N=262144) and the modeled
``hbm_cut_vs_unpruned_ratio``.

Writes BENCH_retrieval.json at the repo root (tracked from PR 5 onward).
"""
from __future__ import annotations

import json
import pathlib

import jax
import jax.numpy as jnp

from repro.core import catalog as catalog_mod
from repro.core.backend import BackendConfig

from .common import emit, timed, run_cpu_child

ROOT = pathlib.Path(__file__).resolve().parents[1]

D, KSHORT = 32, 64
BATCH = 64                     # request-batch users per shortlist call
# (N_items, dense wall-clock comparable) — dense at 2**18 would need a
# [B, N, d] f32 intermediate (2 GiB at B=64): modeled only, like
# bench_graph's dense_at_65536.  The gated 262144 row stays shapes[0] in
# both modes so check_regression's baseline paths line up.
FULL_SHAPES = [(262144, False), (16384, True)]
QUICK_SHAPES = [(262144, False)]
REFERENCE_1M = 1 << 20


# ---- analytic HBM-traffic model (f32 words per user per request) -----------

def hbm_words_dense(N: int, d: int, batch: int) -> float:
    """Dense [B, N] scoring, op-level accounting (see module docstring)."""
    return N * d / batch + 2 * N * d + 2 * N + d * d + d


def hbm_words_streaming(N: int, d: int, k_short: int, block_users: int
                        ) -> float:
    """Streaming engine: catalog once per user block, shortlist out."""
    return N * d / block_users + d * d + d + 4 * k_short


def hbm_words_pruned(N: int, d: int, k_short: int, block_users: int,
                     tiles: int, skip_ratio: float) -> float:
    """Cluster-pruned streaming: only ``(1 - skip_ratio)`` of the catalog
    streams; adds the ``[T, d+3]`` cluster-bound table (read once per
    user block) and the per-user ``[T]`` tile-bound row."""
    return ((1.0 - skip_ratio) * N * d / block_users
            + tiles * (d + 3) / block_users + tiles
            + d * d + d + 4 * k_short)


# ---- modeled sharded comm (f32 words per request batch) --------------------

def comm_words_sharded(batch: int, d: int, k_short: int, shards: int) -> int:
    """Two-stage merge traffic: psum-replicate the request users' stats
    (d^2 + d + 1 words each), all-gather the per-shard (score, id)
    shortlists, psum the one-hot shortlist-embedding assembly."""
    return (batch * (d * d + d + 1)
            + 2 * batch * k_short * shards
            + batch * k_short * d)


def comm_words_dense(batch: int, N: int) -> int:
    """The alternative: ship every shard's [B, N_local] scores to a
    merger — O(B N) words regardless of topology."""
    return batch * N


# ---- timed rows ------------------------------------------------------------

def _inputs(n, d, N, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    w = 0.1 * jax.random.normal(ks[0], (n, d))
    Minv = jnp.broadcast_to(jnp.eye(d, dtype=jnp.float32), (n, d, d))
    occ = jax.random.randint(ks[1], (n,), 1, 100)
    cat = catalog_mod.random_catalog(ks[2], N, d)
    return w, Minv, occ, cat


def _dense_topk(w, Minv, occ, items, alpha, k):
    est = jnp.einsum("nd,Nd->nN", w, items)
    t = jnp.einsum("nab,Nb->nNa", Minv, items)
    quad = jnp.einsum("nNa,Na->nN", t, items)
    s = est + alpha * jnp.sqrt(jnp.maximum(quad, 0.0)) * jnp.sqrt(
        jnp.log1p(occ.astype(jnp.float32)))[:, None]
    return jax.lax.top_k(s, k)


def bench_shape(N, dense_ok, repeats=2):
    w, Minv, occ, cat = _inputs(BATCH, D, N)
    rb = BackendConfig.create("reference").retrieval(D, KSHORT)
    f_stream = jax.jit(lambda w, M, o, e, lv: rb.shortlist(
        w, M, o, e, lv, 0.3))
    ids = f_stream(w, Minv, occ, cat.serving.emb, cat.serving.live)[1]
    jax.block_until_ready(ids)
    secs, _ = timed(f_stream, w, Minv, occ, cat.serving.emb, cat.serving.live,
                    repeats=repeats)

    rec = {
        "N_items": N, "batch": BATCH, "d": D, "K_short": KSHORT,
        "backend": "reference",
        "streaming_us": 1e6 * secs,
        "hbm_bytes_per_user_dense": 4 * hbm_words_dense(N, D, BATCH),
        "hbm_bytes_per_user_streaming": 4 * hbm_words_streaming(
            N, D, KSHORT, rb.block_users),
        "hbm_cut_ratio": hbm_words_dense(N, D, BATCH)
        / hbm_words_streaming(N, D, KSHORT, rb.block_users),
        "comm_bytes_sharded8_per_batch": 4 * comm_words_sharded(
            BATCH, D, KSHORT, 8),
        "comm_bytes_dense_gather_per_batch": 4 * comm_words_dense(BATCH, N),
        "comm_cut_ratio": comm_words_dense(BATCH, N)
        / comm_words_sharded(BATCH, D, KSHORT, 8),
    }
    if dense_ok:
        f_dense = jax.jit(lambda w, M, o, e: _dense_topk(
            w, M, o, e, 0.3, KSHORT))
        jax.block_until_ready(f_dense(w, Minv, occ, cat.serving.emb))
        dsecs, _ = timed(f_dense, w, Minv, occ, cat.serving.emb, repeats=repeats)
        rec["dense_us"] = 1e6 * dsecs
    else:
        rec["dense_skipped"] = (
            f"dense scoring needs a [B, N, d] f32 intermediate "
            f"({4 * BATCH * N * D / 2**30:.1f} GiB) — modeled only")
    emit(f"retrieval_topk_N{N}_B{BATCH}_streaming", rec["streaming_us"],
         f"hbm_cut={rec['hbm_cut_ratio']:.1f}x")
    return rec


def _reference_1m_row(repeats=1):
    """N_items = 2**20 on one CPU core: the row-blocked oracle at a
    small request batch — the catalog-scale acceptance row."""
    n = 8
    w, Minv, occ, cat = _inputs(n, D, REFERENCE_1M)
    rb = BackendConfig.create("reference").retrieval(D, KSHORT)
    f = jax.jit(lambda w, M, o, e, lv: rb.shortlist(w, M, o, e, lv, 0.3))
    out = f(w, Minv, occ, cat.serving.emb, cat.serving.live)
    jax.block_until_ready(out)
    secs, _ = timed(f, w, Minv, occ, cat.serving.emb, cat.serving.live, repeats=repeats)
    emit(f"retrieval_topk_N{REFERENCE_1M}_B{n}_reference", 1e6 * secs,
         "catalog=2**20")
    return {"N_items": REFERENCE_1M, "batch": n, "d": D, "K_short": KSHORT,
            "backend": "reference", "completes_on_cpu": True,
            "streaming_us": 1e6 * secs}


def _pruned_row(N=262144, tile_items=512, repeats=1):
    """Cluster-pruned vs plain streaming on a region-structured catalog
    (8 regions, tight item noise — the regime cluster pruning targets;
    a structureless catalog degrades to ~0 skips, never to wrong
    results).

    The exactness check runs the SAME compiled kernel twice — real tile
    bounds vs ``tb = +inf`` (skipping disabled) — and requires bit-equal
    (score, id) shortlists.  That isolates the pruning logic: two
    separately-compiled programs can differ in the last ulp from XLA
    reduction reassociation, which flips near-ties and is not a property
    of pruning (the serving path keeps both branches in one ``lax.cond``
    program for the same reason; see tests/test_itemclub.py).  The
    no-skip run doubles as the apples-to-apples unpruned wall-clock.
    Raises if pruning is inexact or the skip ratio misses the 0.5
    acceptance floor, so run.py's failure policy gates it."""
    import numpy as np

    from repro.core import env as env_mod
    from repro.core import itemclub
    from repro.kernels.topk.ops import topk_pruned
    from repro.kernels.topk.ref import tile_bounds

    e, _ = env_mod.make_catalog_env(jax.random.PRNGKey(0), BATCH, D, 8, N,
                                    item_noise_scale=0.01)
    cat = catalog_mod.make_catalog(env_mod.catalog_embeddings(e))
    w = e.theta                      # unit-ish user params: realistic floors
    Minv = jnp.broadcast_to(jnp.eye(D, dtype=jnp.float32), (BATCH, D, D))
    occ = jax.random.randint(jax.random.PRNGKey(1), (BATCH,), 1, 100)

    build_secs, cl = timed(itemclub.build_clusters, cat,
                           tile_items=tile_items, n_anchors=512)

    f = jax.jit(lambda w, M, o, c, tb: topk_pruned(
        w, M, o, c.emb_sorted, c.live_sorted, c.perm, 0.3, KSHORT, tb,
        use_pallas=False, row_block=4))
    tb = tile_bounds(w, Minv, occ, 0.3, cl.tile_mu, cl.tile_r,
                     cl.tile_xn, cl.tile_n)
    tb_off = jnp.full_like(tb, jnp.inf)

    jax.block_until_ready(f(w, Minv, occ, cl, tb))
    p_secs, (sp, ip, skipped, total) = timed(f, w, Minv, occ, cl, tb,
                                             repeats=repeats)
    jax.block_until_ready(f(w, Minv, occ, cl, tb_off))
    u_secs, (su, iu, _, _) = timed(f, w, Minv, occ, cl, tb_off,
                                   repeats=repeats)

    identical = bool(np.array_equal(np.asarray(iu), np.asarray(ip))
                     and np.array_equal(np.asarray(su), np.asarray(sp)))
    ratio = float(skipped) / float(total)
    if not identical:
        raise RuntimeError("pruned shortlist diverged from the no-skip "
                           "run of the same kernel — the exactness "
                           "invariant is broken")
    if ratio < 0.5:
        raise RuntimeError(
            f"tiles_skipped_ratio {ratio:.3f} < 0.5 acceptance floor")

    tiles = N // tile_items
    bu = 128                    # engine user-block (matches shapes rows)
    words_un = hbm_words_streaming(N, D, KSHORT, bu)
    words_pr = hbm_words_pruned(N, D, KSHORT, bu, tiles, ratio)
    rec = {
        "N_items": N, "batch": BATCH, "d": D, "K_short": KSHORT,
        "backend": "reference", "scenario": "regions8_noise0.01",
        "tile_items": tile_items,
        "tiles_skipped_ratio": ratio,
        "pruned_ids_identical": identical,
        "pruned_us": 1e6 * p_secs,
        "unpruned_us": 1e6 * u_secs,
        "cluster_build_us": 1e6 * build_secs,
        "hbm_bytes_per_user_pruned": 4 * words_pr,
        "hbm_cut_vs_unpruned_ratio": words_un / words_pr,
    }
    emit(f"retrieval_pruned_N{N}_B{BATCH}", rec["pruned_us"],
         f"skip={ratio:.2f},unpruned_us={rec['unpruned_us']:.0f}")
    return rec


def _interpret_parity(n=16, d=16, N=512, k=8):
    """In-run validation that the kernel path matches the oracle bit for
    bit (full coverage in tests/test_retrieval.py)."""
    import numpy as np

    w, Minv, occ, cat = _inputs(n, d, N, seed=3)
    live = cat.serving.live.at[jnp.arange(0, N, 7)].set(0.0)
    r_ref = BackendConfig.create("reference").retrieval(d, k)
    r_pal = BackendConfig.create("pallas").retrieval(
        d, k, block_users=8, block_items=128, interpret=True)
    s1, i1 = r_ref.shortlist(w, Minv, occ, cat.serving.emb, live, 0.3)
    s2, i2 = r_pal.shortlist(w, Minv, occ, cat.serving.emb, live, 0.3)
    return {
        "ids_identical": bool((np.asarray(i1) == np.asarray(i2)).all()),
        "scores_max_abs_err": float(jnp.max(jnp.abs(s1 - s2))),
        "pallas_backend": "pallas_interpret"
        if jax.default_backend() != "tpu" else "pallas",
    }


_SHARDED_CODE = r"""
import time, jax, jax.numpy as jnp
from repro import serve
from repro.core import catalog as catalog_mod, env
from repro.core.types import BanditHyper
from repro.distributed.distclub_shard import named_shardings

N_USERS, D, KS, B, N_ITEMS = 1024, {d}, {ks}, {batch}, {n_items}
hyper = BanditHyper(alpha=0.05, gamma=1.5, n_candidates=KS)
e, _ = env.make_catalog_env(jax.random.PRNGKey(0), N_USERS, D, 8, N_ITEMS)
cat = serve.make_catalog(env.catalog_embeddings(e))
theta = e.theta

def reward_fn(key, uids, ctx, choice):
    return env.step_rewards(key, theta[uids], ctx, choice)

from repro.launch.mesh import make_mesh
mesh = make_mesh((8,), ("users",))
session = serve.OnlineBandit.sharded(mesh, N_USERS, D, hyper,
                                     policy="distclub", refresh_every=0,
                                     backend="reference")
cat8 = jax.device_put(cat, named_shardings(mesh,
                                           catalog_mod.specs(("users",))))
uids = jax.random.permutation(jax.random.PRNGKey(2),
                              N_USERS)[:B].astype(jnp.int32)
session, ids, m = serve.step_catalog(session, jax.random.PRNGKey(3), uids,
                                     cat8, reward_fn, k_short=KS)
jax.block_until_ready(ids)
t0 = time.perf_counter()
REP = 3
for i in range(REP):
    session, ids, m = serve.step_catalog(session, jax.random.PRNGKey(4 + i),
                                         uids, cat8, reward_fn, k_short=KS)
jax.block_until_ready(ids)
print("SHARD_STEP_US", 1e6 * (time.perf_counter() - t0) / REP)
"""


def _sharded_row(n_items=65536, batch=32):
    """8-device item-sharded two-stage serving transaction (host-platform
    mesh; 8 shards on one CPU core, so wall-clock is a smoke number —
    the modeled comm cut is the tracked metric)."""
    stdout = run_cpu_child(
        _SHARDED_CODE.format(d=D, ks=KSHORT, batch=batch, n_items=n_items),
        8)
    us = float(stdout.split("SHARD_STEP_US")[1].split()[0])
    emit(f"retrieval_step_sharded8_N{n_items}_B{batch}", us,
         f"comm_cut={comm_words_dense(batch, n_items) / comm_words_sharded(batch, D, KSHORT, 8):.1f}x")
    return {
        "N_items": n_items, "batch": batch, "d": D, "K_short": KSHORT,
        "step_us": us, "platform": "cpu",
        "comm_bytes_merge_per_batch": 4 * comm_words_sharded(
            batch, D, KSHORT, 8),
        "comm_bytes_dense_gather_per_batch": 4 * comm_words_dense(
            batch, n_items),
        "comm_cut_ratio": comm_words_dense(batch, n_items)
        / comm_words_sharded(batch, D, KSHORT, 8),
    }


def main(quick: bool = False):
    shapes = QUICK_SHAPES if quick else FULL_SHAPES
    records = [bench_shape(N, dense_ok, repeats=1 if quick else 2)
               for (N, dense_ok) in shapes]
    gate = next(r for r in records if r["N_items"] == 262144)
    payload = {
        "mode": "quick" if quick else "full",
        "jax_backend": jax.default_backend(),
        "hbm_model_note": (
            "per-user f32 words; dense = [B,N] op chain with a [N,d] "
            "quad intermediate per user; streaming = catalog once per "
            "user block + d^2 state + the [K_short] shortlist (see "
            "module docstring / README 'Catalog-scale retrieval')"),
        "shapes": records,
        "reference_1M": _reference_1m_row(),
        "sharded_8dev": _sharded_row(),
        # own top-level dict: its identity keys overlap shapes[0]'s, and
        # check_regression paths must stay collision-free
        "pruned": _pruned_row(repeats=1 if quick else 2),
        "interpret_parity": _interpret_parity(),
        # the headline gated scalar is shape-PINNED (the acceptance row),
        # not a min over the mode-dependent shape list — quick and full
        # runs must agree on every gated value
        "hbm_cut_ratio_at_262144": gate["hbm_cut_ratio"],
    }
    (ROOT / "BENCH_retrieval.json").write_text(json.dumps(payload, indent=1))
    return payload


if __name__ == "__main__":
    main()
