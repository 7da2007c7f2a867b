"""The seven main-path Pallas kernels compile for a TPU v5e chip.

Each kernel is lowered and compiled (not run) for one chip of a
described ``v5e:2x2`` topology at the widths ``chip_smoke.py`` drives:
the paper's synthetic set (20,480 users, d=25 -> 32, K=20 -> 128) for
choose, the rank-1 fold and the batched SPD inverse, and catalog serving (B=1,024 requests over
2^20 items at d=32, K_short=64; a stage-2 refresh over 65,536 users) for
the top-K streams and the graph kernels.  Interpret mode accepts layouts
the chip's compiler refuses (1-D blocks, batched ``dot_general``,
unsigned reductions, lane-splitting reshapes), so these compiles are
what guards the chip path without a chip.

The topology is described inside a module fixture, never at import:
only one process may load the TPU compiler library at a time, and every
test worker imports this file.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import distclub_paper as paper
from repro.kernels import pad
from repro.kernels.graph.graph import cc_hop_packed_pallas, prune_packed_pallas
from repro.kernels.interact.interact import choose_pallas
from repro.kernels.rank1.rank1 import rank1_update_inv_pallas
from repro.kernels.spdinv.ops import spd_inverse
from repro.kernels.topk.ref import tile_bounds
from repro.kernels.topk.topk import topk_pallas, topk_pruned_pallas

# paper phase: padded (n, d, K) of the fused engine
N_PAPER, D_PAD, K_PAD, _ = pad.padded_dims(
    paper.N_USERS, paper.D_FEAT, paper.CONFIG.n_candidates)
# serve phase
BATCH, N_ITEMS, D, K_SHORT, N_GRAPH, TILE = 1024, 2 ** 20, 32, 64, 65536, 512
ALPHA, GAMMA = 0.03, 1.6


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler library / topology here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _cases(S):
    f32, i32, u32 = jnp.float32, jnp.int32, jnp.uint32
    T = N_ITEMS // TILE
    return {
        "choose": lambda: jax.jit(
            lambda w, M, c, o: choose_pallas(w, M, c, o, ALPHA, 20)).lower(
            S((N_PAPER, D_PAD)), S((N_PAPER, D_PAD, D_PAD)),
            S((N_PAPER, K_PAD, D_PAD)), S((N_PAPER,), i32)),
        "rank1_update_inv": lambda: jax.jit(rank1_update_inv_pallas).lower(
            S((N_PAPER, D_PAD, D_PAD)), S((N_PAPER, D_PAD)),
            S((N_PAPER, D_PAD)), S((N_PAPER,)), S((N_PAPER,))),
        "spd_inverse": lambda: jax.jit(
            lambda a: spd_inverse(a, use_pallas=True, interpret=False)).lower(
            S((paper.N_USERS, paper.D_FEAT, paper.D_FEAT))),
        "topk": lambda: jax.jit(
            lambda w, M, o, it, lv: topk_pallas(
                w, M, o, it, lv, ALPHA, K_SHORT)).lower(
            S((BATCH, D)), S((BATCH, D, D)), S((BATCH,), i32),
            S((N_ITEMS, D)), S((N_ITEMS,))),
        "topk_pruned": lambda: jax.jit(
            lambda w, M, o, it, lv, ids, tb: topk_pruned_pallas(
                w, M, o, it, lv, ids, tb, ALPHA, K_SHORT,
                block_items=TILE)).lower(
            S((BATCH, D)), S((BATCH, D, D)), S((BATCH,), i32),
            S((N_ITEMS, D)), S((N_ITEMS,)), S((N_ITEMS,), i32),
            S((BATCH, T))),
        "graph_prune": lambda: jax.jit(
            lambda p, vi, ci, vj, cj: prune_packed_pallas(
                p, vi, ci, vj, cj, GAMMA)).lower(
            S((N_GRAPH, N_GRAPH // 32), u32), S((N_GRAPH, D)),
            S((N_GRAPH,)), S((N_GRAPH, D)), S((N_GRAPH,))),
        "cc_hop": lambda: jax.jit(cc_hop_packed_pallas).lower(
            S((N_GRAPH, N_GRAPH // 32), u32), S((N_GRAPH,), i32),
            S((N_GRAPH,), i32)),
    }


@pytest.mark.parametrize("kernel", ["choose", "rank1_update_inv",
                                    "spd_inverse", "topk", "topk_pruned",
                                    "graph_prune", "cc_hop"])
def test_kernel_compiles_for_v5e(kernel, one_chip, no_compile_cache):
    S = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(
        shape, dt, sharding=one_chip)
    compiled = _cases(S)[kernel]().compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert kernel in text


@pytest.mark.parametrize("d", [19, 32])
def test_tile_bounds_compile_without_eigensolver(d, one_chip,
                                                 no_compile_cache):
    """The per-request tile bounds at the serving widths (B=1,024 requests,
    123 tiles: MovieLens-25M's catalog) compile to plain XLA ops: no
    eigensolver (``EighTpu``), nor any custom call but the compiler's own
    ``ConcatBitcast`` layout op, bounds the inverse Grams' largest
    eigenvalue."""
    import re
    S = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(
        shape, dt, sharding=one_chip)
    T = 123
    text = tile_bounds.lower(
        S((BATCH, d)), S((BATCH, d, d)), S((BATCH,), jnp.int32), ALPHA,
        S((T, d)), S((T,)), S((T,)), S((T,), jnp.int32)).compile().as_text()
    targets = set(re.findall(r'custom_call_target="([^"]+)"', text))
    assert targets <= {"ConcatBitcast"}, targets
    assert "eigh" not in text.lower()


def _serve_reward(key, uids, ctx, slot):
    return jax.random.bernoulli(key, 0.5, uids.shape).astype(jnp.float32)


def test_donated_fold_copies_no_table_per_pass(one_chip, no_compile_cache,
                                               monkeypatch):
    """The donated catalog transaction at d=19, where a TPU stores the
    ``[n, 19, 19]`` inverse-Gram table with its users on the lanes: the
    fold's passes scatter into a row-major table, so the compiled program
    copies no ``[n, 19, 19]`` table inside a loop (unfixed, XLA copied the
    whole table into the gather's layout on every pass)."""
    import re
    from repro import serve
    from repro.core.types import BanditHyper
    from repro.serve import policies as pol
    from repro.serve import session as session_mod
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    n, d, items, batch = 20480, 19, 4096, BATCH
    cfg = pol.make_cfg(n, d, BanditHyper(alpha=ALPHA, gamma=GAMMA,
                                         n_candidates=K_SHORT),
                       refresh_every=16 * batch, backend="pallas",
                       interpret=False, precision="f32")
    policy = pol.get_policy("distclub", cfg)
    S = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
    state = jax.tree.map(S, jax.eval_shape(policy.init))
    cat = jax.tree.map(S, jax.eval_shape(
        lambda e: serve.make_catalog(e, precision="f32"),
        jax.ShapeDtypeStruct((items, d), jnp.float32)))
    clusters = jax.tree.map(S, jax.eval_shape(
        lambda c: serve.build_clusters(c, tile_items=TILE, kind="reference"),
        cat))

    def stored(leaf):
        if leaf.ndim < 2:
            return None
        return (jax.jit(lambda x: x).lower(leaf).compile()
                .input_formats[0][0].layout.major_to_minor)
    layouts = tuple(stored(leaf) for leaf in jax.tree.leaves(state))
    assert layouts[0] == (1, 2, 0)             # Minv: users on the lanes
    rb = session_mod._retrieval_engine(
        serve.OnlineBandit(policy=policy, state=None), K_SHORT)
    fn = session_mod._catalog_step_fn(policy, rb, _serve_reward, None, (),
                                      True, True, layouts)
    text = fn.lower(state, S(jnp.zeros((2,), jnp.uint32)),
                    S(jnp.zeros((batch,), jnp.int32)), cat,
                    clusters).compile().as_text()
    assert "input_output_alias={ {0}: (0" in text       # the state donated
    bodies = set(re.findall(r"body=(%[\w.\-]+)", text))
    where, table_copies = None, []
    for line in text.splitlines():
        if line.startswith("%") or line.startswith("ENTRY"):
            where = line.split()[0]
        if re.match(rf"\s*%copy[\w.\-]* = f32\[{n},{d},{d}\]", line):
            table_copies.append(where)
    assert table_copies and not [w for w in table_copies if w in bodies], \
        table_copies
