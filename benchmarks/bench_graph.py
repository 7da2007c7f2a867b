"""Stage-2 graph-engine benchmark: dense vs bit-packed adjacency.

Times the two stage-2 graph sweeps — the CLUB edge-prune and one
connected-components hop — at n in {1k, 4k, 16k, 64k}, and reports the
modeled HBM bytes of a full stage-2 refresh (prune + ceil(log2 n)+1
pointer-doubling hops) for both representations.

HBM model (op-level, matching bench_interact's accounting style —
"each XLA op streams its operands"; elementwise chains assumed fused):

  dense prune   8 n^2   [n, n] f32 distance matrix write + read
              + 2 n^2   bool adjacency read + write
              + 8 n d   user vectors
  dense hop     n^2     bool adjacency read
              + 8 n^2   [n, n] i32 neighbour-label intermediate w + r
              + 12 n    label read / pointer-double gather / write
  packed prune  2 n^2/8 packed adjacency read + write — the distance
                        tile lives and dies in VMEM
              + 4 n d (n/Bi + 1)  v_j tile re-streamed once per row block
  packed hop    n^2/8   packed adjacency read
              + 4 n (n/Bi)        column labels per row block
              + 12 n

The dense graph is additionally 32x larger *resident*: n^2 bool vs
n^2/8 packed bytes — at n=65536 the dense path needs a 4.3 GB adjacency
plus a 17 GB f32 distance matrix, so it is skipped above DENSE_N_CAP and
recorded as such; the packed path must (and does) complete on one CPU
host.  Wall-clock off-TPU runs the blocked reference engine — the same
row-tiled schedule the Pallas kernels execute per-grid-step on TPU.

Writes BENCH_graph.json at the repo root (tracked from PR 2 onward).
"""
from __future__ import annotations

import json
import math
import pathlib

import jax
import jax.numpy as jnp

from repro.core import backend as backend_mod
from repro.core import clustering
from repro.kernels.graph import ops as graph_ops

from .common import emit, timed

ROOT = pathlib.Path(__file__).resolve().parents[1]
KEY = jax.random.PRNGKey(0)

NS = [1024, 4096, 16384, 65536]
D = 16
BLOCK_I = graph_ops.BLOCK_I
# dense needs ~n^2 * 9 transient bytes (adj + i32/f32 [n,n] intermediates):
# ~2.4 GB at 16384, ~39 GB at 65536 — cap it where the packed path keeps going.
DENSE_N_CAP = 16384
GAMMA = 0.9


# ---- analytic HBM model (bytes per stage-2 refresh) -------------------------

def cc_hops(n: int) -> int:
    """Static bound on pointer-doubling hops to convergence."""
    return max(1, math.ceil(math.log2(max(n, 2))) + 1)


def hbm_bytes_dense(n: int, d: int) -> int:
    prune = 8 * n * n + 2 * n * n + 8 * n * d
    hop = n * n + 8 * n * n + 12 * n
    return prune + cc_hops(n) * hop


def hbm_bytes_packed(n: int, d: int, block_i: int = BLOCK_I) -> int:
    row_blocks = -(-n // block_i)
    prune = 2 * (n * n // 8) + 4 * n * d * (row_blocks + 1)
    hop = n * n // 8 + 4 * n * row_blocks + 12 * n
    return prune + cc_hops(n) * hop


# ---- timed sweeps -----------------------------------------------------------

def _inputs(n, d):
    ks = jax.random.split(KEY, 3)
    v = jax.random.normal(ks[0], (n, d)) * 0.1
    occ = jax.random.randint(ks[1], (n,), 1, 200)
    labels = jnp.arange(n, dtype=jnp.int32)
    return v, occ, labels


def _dense_hop(adj, labels):
    """One dense min-label hop + pointer doubling (the seed CC body)."""
    n = adj.shape[0]
    neigh = jnp.where(adj, labels[None, :], jnp.int32(n))
    l1 = jnp.minimum(labels, jnp.min(neigh, axis=1))
    return jnp.minimum(l1, l1[l1])


def bench_dense(n, d, repeats):
    v, occ, labels = _inputs(n, d)
    adj = clustering.dense_adj(n)
    f_prune = jax.jit(lambda a, v, o: clustering.prune_edges(a, v, o, GAMMA))
    f_hop = jax.jit(_dense_hop)
    pruned = f_prune(adj, v, occ)                 # compile
    f_hop(pruned, labels)
    t_prune, _ = timed(f_prune, adj, v, occ, repeats=repeats)
    t_hop, _ = timed(f_hop, pruned, labels, repeats=repeats)
    return {"skipped": False, "prune_us": 1e6 * t_prune,
            "cc_hop_us": 1e6 * t_hop}


def _packed_hop(gb, adj, labels):
    """One packed min-label hop + pointer doubling."""
    l1 = gb.cc_hop(adj, labels, labels)
    return jnp.minimum(l1, l1[l1])


def bench_packed(n, d, repeats):
    v, occ, labels = _inputs(n, d)
    gb = backend_mod.BackendConfig.create().graph(n)
    adj = gb.init_adj()
    f_prune = jax.jit(lambda a, v, o: gb.prune(a, v, o, GAMMA))
    f_hop = jax.jit(lambda a, l: _packed_hop(gb, a, l))
    pruned = f_prune(adj, v, occ)                 # compile
    f_hop(pruned, labels)
    t_prune, _ = timed(f_prune, adj, v, occ, repeats=repeats)
    t_hop, _ = timed(f_hop, pruned, labels, repeats=repeats)
    rec = {"backend": gb.kind, "prune_us": 1e6 * t_prune,
           "cc_hop_us": 1e6 * t_hop,
           "adj_bytes": int(n * gb.words * 4)}
    if n <= 4096:
        # full CC to convergence is cheap enough to track at small n
        f_cc = jax.jit(gb.cc)
        f_cc(pruned)
        t_cc, _ = timed(f_cc, pruned, repeats=repeats)
        rec["cc_full_us"] = 1e6 * t_cc
    return rec


def bench_shape(n, d, repeats=2):
    repeats = 1 if n > 16384 else repeats
    model = {
        "dense_stage2_bytes": hbm_bytes_dense(n, d),
        "packed_stage2_bytes": hbm_bytes_packed(n, d),
        "cc_hops": cc_hops(n),
    }
    model["hbm_reduction"] = (model["dense_stage2_bytes"]
                              / model["packed_stage2_bytes"])
    if n <= DENSE_N_CAP:
        dense = bench_dense(n, d, repeats)
    else:
        dense = {"skipped": True,
                 "reason": f"dense graph needs ~{9 * n * n / 1e9:.0f} GB of "
                           "[n,n] intermediates (adjacency + f32 distance + "
                           "i32 neighbour labels); packed runs in "
                           f"{n * n // 8 / 1e9:.1f} GB"}
    packed = bench_packed(n, d, repeats)
    rec = {
        "n": n, "d": d,
        "graph_mem_dense_bytes": n * n,
        "graph_mem_packed_bytes": int(n * ((n + 31) // 32) * 4),
        "dense": dense, "packed": packed, "model": model,
    }
    emit(f"graph_prune_n{n}_packed", packed["prune_us"],
         f"hbm_reduction={model['hbm_reduction']:.1f}x")
    emit(f"graph_cc_hop_n{n}_packed", packed["cc_hop_us"],
         "dense=skipped" if dense.get("skipped")
         else f"dense_us={dense['cc_hop_us']:.1f}")
    return rec


def _interpret_parity(n=150, d=8):
    """In-run check: pallas-interpret prune + CC equal the reference engine
    (full parity matrix lives in tests/test_graph.py)."""
    import numpy as np

    v, occ, labels = _inputs(n, d)
    ref = backend_mod.BackendConfig.create("reference").graph(n)
    pal = backend_mod.BackendConfig.create("pallas").graph(n, interpret=True)
    adj0 = ref.init_adj()
    a_ref = ref.prune(adj0, v, occ, GAMMA)
    a_pal = pal.prune(adj0, v, occ, GAMMA)
    same_adj = bool((np.asarray(a_ref) == np.asarray(a_pal)).all())
    same_cc = bool((np.asarray(ref.cc(a_ref))
                    == np.asarray(pal.cc(a_pal))).all())
    return {"pruned_bits_identical": same_adj, "cc_labels_identical": same_cc}


def main(quick: bool = False):
    # the acceptance gates live at n=16384 (modeled >=8x) and n=65536
    # (packed completes where dense cannot), so --quick runs the full n
    # sweep; "quick" trims repeats, not coverage.
    records = [bench_shape(n, D, repeats=2 if quick else 3) for n in NS]
    by_n = {r["n"]: r for r in records}
    payload = {
        "mode": "quick" if quick else "full",
        "jax_backend": jax.default_backend(),
        "block_i": BLOCK_I,
        "records": records,
        "interpret_parity": _interpret_parity(),
        "hbm_reduction_at_16384": by_n[16384]["model"]["hbm_reduction"],
        "packed_completes_at_65536": 65536 in by_n
                                     and "prune_us" in by_n[65536]["packed"],
        "dense_at_65536": by_n[65536]["dense"],
    }
    (ROOT / "BENCH_graph.json").write_text(json.dumps(payload, indent=1))
    return payload


if __name__ == "__main__":
    main()
