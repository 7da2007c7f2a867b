"""Stage-2 graph engine: bit-packed adjacency, tiled prune, fused CC hop.

All Pallas runs use interpret=True (no TPU in this container) with small
block sizes so every test exercises a multi-tile grid; the same code path
compiles on TPU with interpret=False.  Parity against the dense oracle is
EXACT (bit/label equality): the feature dim is the only contracted axis,
so tiling over (i, j) cannot change any per-element contraction order.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import backend, clustering, distclub, env, env_ops
from repro.core.types import BanditHyper
from repro.kernels.graph import ops as graph_ops


def random_sym_adj(rng, n, p):
    a = rng.random((n, n)) < p
    a = np.triu(a, 1)
    return a | a.T


def chain_adj(n):
    """Path graph 0-1-...-n-1: one component, max-diameter — the
    pointer-doubling worst case."""
    a = np.zeros((n, n), bool)
    i = np.arange(n - 1)
    a[i, i + 1] = a[i + 1, i] = True
    return a


# ---- packing ---------------------------------------------------------------

@pytest.mark.parametrize("n", [1, 7, 32, 37, 100, 256])
def test_pack_unpack_roundtrip(n):
    rng = np.random.default_rng(n)
    dense = random_sym_adj(rng, n, 0.3)
    packed = graph_ops.pack_bits(jnp.asarray(dense))
    assert packed.shape == (n, (n + 31) // 32) and packed.dtype == jnp.uint32
    np.testing.assert_array_equal(
        np.asarray(graph_ops.unpack_bits(packed, n)), dense)


def test_pack_padding_bits_are_zero():
    """Bits at columns >= n must be 0 — the AND-monotone invariant."""
    n = 37
    dense = jnp.ones((n, n), bool)
    packed = graph_ops.pack_bits(dense)
    full = graph_ops.unpack_bits(packed, packed.shape[1] * 32)
    assert not bool(full[:, n:].any())


@pytest.mark.parametrize("n", [5, 33, 64, 100])
def test_init_packed_adj_matches_dense(n):
    got = graph_ops.unpack_bits(graph_ops.init_packed_adj(n, n), n)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(clustering.dense_adj(n)))


def test_init_packed_adj_row_offset():
    """Sharded rows clear their own global column, not the local index."""
    n, n_local, off = 64, 16, 16
    got = graph_ops.unpack_bits(
        graph_ops.init_packed_adj(n_local, n, row_offset=off), n)
    want = np.ones((n_local, n), bool)
    want[np.arange(n_local), np.arange(n_local) + off] = False
    np.testing.assert_array_equal(np.asarray(got), want)


# ---- prune -----------------------------------------------------------------

# Ragged on purpose: n not a multiple of 32 nor of the block sizes.
@pytest.mark.parametrize("n,d", [(37, 5), (70, 8), (130, 3)])
def test_prune_packed_matches_dense_oracle(n, d):
    rng = np.random.default_rng(n * 10 + d)
    v = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    occ = jnp.asarray(rng.integers(0, 100, n), jnp.int32)
    dense0 = random_sym_adj(rng, n, 0.7)
    want = clustering.prune_edges(jnp.asarray(dense0), v, occ, gamma=1.2)

    packed = _stored(graph_ops.pack_bits(jnp.asarray(dense0)), n)
    cb = clustering.cb_width(occ)
    for kwargs in (
        dict(use_pallas=False, row_block=16),
        dict(use_pallas=True, interpret=True),
    ):
        got = graph_ops.unpack_bits(graph_ops.user_rows(
            graph_ops.prune_packed(packed, v, cb, v, cb, 1.2, **kwargs), n),
            n)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want),
                                      err_msg=str(kwargs))


def test_prune_is_and_monotone():
    """Pruning can only clear bits, never set them (packing invariant)."""
    n, d = 50, 4
    rng = np.random.default_rng(0)
    v = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    occ = jnp.full((n,), 1000, jnp.int32)
    dense0 = random_sym_adj(rng, n, 0.2)
    packed = graph_ops.pack_bits(jnp.asarray(dense0))
    cb = clustering.cb_width(occ)
    out = graph_ops.prune_packed(packed, v, cb, v, cb, 0.5, use_pallas=False)
    assert not bool((np.asarray(out) & ~np.asarray(packed)).any())


# ---- connected components --------------------------------------------------

@pytest.mark.parametrize("maker,n", [
    ("random_sparse", 60), ("random_sparse", 129), ("random_dense", 75),
    ("chain", 300), ("chain", 64), ("empty", 40),
])
def test_cc_packed_matches_dense(maker, n):
    rng = np.random.default_rng(n)
    dense = {"random_sparse": lambda: random_sym_adj(rng, n, 0.02),
             "random_dense": lambda: random_sym_adj(rng, n, 0.3),
             "chain": lambda: chain_adj(n),
             "empty": lambda: np.zeros((n, n), bool)}[maker]()
    want = clustering.connected_components(jnp.asarray(dense))
    packed = _stored(graph_ops.pack_bits(jnp.asarray(dense)), n)
    gb_ref = backend.BackendConfig.create("reference").graph(n,
                                                             row_block=16)
    gb_pal = backend.BackendConfig.create("pallas").graph(n, interpret=True)
    np.testing.assert_array_equal(np.asarray(gb_ref.cc(packed)),
                                  np.asarray(want))
    np.testing.assert_array_equal(np.asarray(gb_pal.cc(packed)),
                                  np.asarray(want))


def test_cc_hop_bipartite_rows():
    """The sharded runtime runs the hop on a row shard against the full
    replicated label vector."""
    n, n_local, off = 96, 32, 32
    rng = np.random.default_rng(7)
    dense = random_sym_adj(rng, n, 0.05)
    labels = jnp.asarray(rng.permutation(n).astype(np.int32))
    rows = jnp.asarray(dense[off:off + n_local])
    want = jnp.minimum(
        labels[off:off + n_local],
        jnp.min(jnp.where(rows, labels[None, :], jnp.int32(n)), axis=1))

    packed_rows = graph_ops.pack_bits(rows)
    for kwargs in (dict(use_pallas=False, row_block=8),
                   dict(use_pallas=True, interpret=True)):
        got = graph_ops.cc_hop_packed(
            packed_rows, labels[off:off + n_local], labels, **kwargs)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want),
                                      err_msg=str(kwargs))


# ---- backend dispatch ------------------------------------------------------

def test_graph_backend_dispatch_and_env_flag(monkeypatch):
    monkeypatch.delenv("REPRO_BACKEND", raising=False)
    gb = backend.BackendConfig.create().graph(100)   # auto on CPU -> ref
    assert gb.kind == "reference" and gb.words == 4

    monkeypatch.setenv("REPRO_BACKEND", "pallas")
    gb = backend.BackendConfig.create().graph(100)
    assert gb.kind == "pallas" and gb.interpret

    monkeypatch.setenv("REPRO_BACKEND", "bogus")
    with pytest.raises(ValueError):
        backend.BackendConfig.create().graph(100)


def test_graph_backend_pack_roundtrip_and_init():
    gb = backend.BackendConfig.create("reference").graph(45)
    dense = clustering.dense_adj(45)
    np.testing.assert_array_equal(np.asarray(gb.unpack(gb.pack(dense))),
                                  np.asarray(dense))
    adj = gb.init_adj()
    # stored at the kernels' padded extents, the padding all zero
    assert adj.shape == graph_ops.stored_shape(45, 45) == (48, 2)
    np.testing.assert_array_equal(np.asarray(gb.unpack(adj[:45])),
                                  np.asarray(dense))
    assert not np.asarray(adj[45:]).any()


# ---- end-to-end ------------------------------------------------------------

def test_distclub_stage2_reference_vs_pallas_interpret():
    """Acceptance: end-to-end distclub agreement between the reference and
    pallas engines now COVERS stage 2 — identical pruned-edge bits,
    identical CC labels, identical cluster counts, and stage-1/3 state
    within PR 1's tolerances."""
    N, D, K = 24, 5, 10
    hyper = BanditHyper(sigma=4, max_rounds=8, gamma=1.5, n_candidates=K)
    e, _ = env.make_synthetic_env(jax.random.PRNGKey(0), N, D, 3, K)
    ops = env_ops.synthetic_ops(e)
    ref_i = backend.BackendConfig.create("reference").interact(N, D, K)
    pal_i = backend.BackendConfig.create("pallas").interact(
        N, D, K, interpret=True)
    ref_g = backend.BackendConfig.create("reference").graph(N)
    pal_g = backend.BackendConfig.create("pallas").graph(N, interpret=True)

    s_r, m_r, c_r = distclub.run(ops, jax.random.PRNGKey(1), hyper,
                                 n_epochs=2, d=D, backend=ref_i, graph=ref_g)
    s_p, m_p, c_p = distclub.run(ops, jax.random.PRNGKey(1), hyper,
                                 n_epochs=2, d=D, backend=pal_i, graph=pal_g)
    np.testing.assert_array_equal(np.asarray(s_p.graph.adj),
                                  np.asarray(s_r.graph.adj))
    np.testing.assert_array_equal(np.asarray(s_p.graph.labels),
                                  np.asarray(s_r.graph.labels))
    np.testing.assert_array_equal(np.asarray(c_p), np.asarray(c_r))
    np.testing.assert_allclose(s_p.lin.Minv, s_r.lin.Minv, atol=1e-5)
    np.testing.assert_allclose(s_p.lin.b, s_r.lin.b, atol=1e-5)
    np.testing.assert_allclose(m_p.reward, m_r.reward, atol=1e-6)


def test_distclub_state_carries_packed_graph():
    """The [n, n] bool graph is gone from the carried state."""
    N, D = 40, 4
    state = distclub.init_state(N, D, BanditHyper())
    assert state.graph.adj.shape == (N, (N + 31) // 32)
    assert state.graph.adj.dtype == jnp.uint32


# ---- the graph stored at the kernels' padded shape -------------------------

def _stored(logical, n):
    """A logical ``[n, ceil(n/32)]`` packed graph laid into its stored
    shape, the padding zero."""
    rows, words = graph_ops.stored_shape(n, n)
    out = np.zeros((rows, words), np.uint32)
    out[:n, :logical.shape[1]] = np.asarray(logical)
    return jnp.asarray(out)


@pytest.mark.parametrize("n", [1000, 4097])
def test_stored_graph_prune_and_hop_match_the_reference(n):
    """User counts neither block divides: the stored (padded) graph run
    through the Pallas kernels as it is gives the real users the prune
    bits and hop labels that ``ref.py`` gives on the logical graph."""
    from repro.kernels.graph import ref
    rng = np.random.default_rng(n)
    d = 5
    v = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    occ = jnp.asarray(rng.integers(0, 40, n).astype(np.int32))
    cb = clustering.cb_width(occ)
    logical = graph_ops.pack_bits(jnp.asarray(random_sym_adj(rng, n, 0.5)))
    stored = _stored(logical, n)
    assert stored.shape != logical.shape
    kw = dict(use_pallas=True, interpret=True)

    want = ref.prune_packed_ref(logical, v, cb, v, cb, 1.6)
    got = graph_ops.prune_packed(stored, v, cb, v, cb, 1.6, **kw)
    assert got.shape == stored.shape
    np.testing.assert_array_equal(np.asarray(graph_ops.user_rows(got, n)),
                                  np.asarray(want))
    assert not np.asarray(got[n:]).any()
    assert not np.asarray(got[:, logical.shape[1]:]).any()

    labels = jnp.asarray(rng.permutation(n).astype(np.int32))
    want = ref.cc_hop_packed_ref(want, labels, labels)
    got = graph_ops.cc_hop_packed(got, labels, labels, **kw)
    assert got.shape == (n,)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_stored_graph_components_match_the_dense_oracle():
    n = 1000
    rng = np.random.default_rng(3)
    dense = random_sym_adj(rng, n, 0.002)
    stored = _stored(graph_ops.pack_bits(jnp.asarray(dense)), n)
    want = clustering.connected_components(jnp.asarray(dense))
    for kind in ("reference", "pallas"):
        gb = backend.BackendConfig.create(kind).graph(n, interpret=True)
        np.testing.assert_array_equal(np.asarray(gb.cc(stored)),
                                      np.asarray(want), err_msg=kind)


@pytest.mark.parametrize("n,shards", [(1000, 1), (4097, 1), (1000, 4)])
def test_init_stored_adj_holds_the_full_graph_per_user(n, shards):
    adj = graph_ops.init_stored_adj(n, shards)
    rows, words = graph_ops.stored_shape(n // shards, n)
    assert adj.shape == (shards * rows, words)
    np.testing.assert_array_equal(
        np.asarray(graph_ops.unpack_bits(
            graph_ops.user_rows(adj, n, shards), n)),
        np.asarray(clustering.dense_adj(n)))
    blocks = np.asarray(adj).reshape(shards, rows, words)
    assert not blocks[:, n // shards:].any()          # padded rows


def test_distclub_stored_graph_reference_vs_pallas_bit_identical():
    """``distclub.run`` at a user count no block divides: the reference
    and Pallas engines carry bit-identical graphs and labels."""
    N, D, K = 1000, 5, 10
    hyper = BanditHyper(sigma=4, max_rounds=8, gamma=0.3, n_candidates=K)
    e, _ = env.make_synthetic_env(jax.random.PRNGKey(0), N, D, 8, K)
    ops = env_ops.synthetic_ops(e)
    out = []
    for kind in ("reference", "pallas"):
        bc = backend.BackendConfig.create(kind)
        st, _, _ = distclub.run(ops, jax.random.PRNGKey(1), hyper,
                                n_epochs=2, d=D,
                                backend=bc.interact(N, D, K, interpret=True),
                                graph=bc.graph(N, interpret=True))
        out.append(st)
    s_r, s_p = out
    assert s_p.graph.adj.shape == graph_ops.stored_shape(N, N)
    np.testing.assert_array_equal(np.asarray(s_p.graph.adj),
                                  np.asarray(s_r.graph.adj))
    np.testing.assert_array_equal(np.asarray(s_p.graph.labels),
                                  np.asarray(s_r.graph.labels))
    assert not np.asarray(s_p.graph.adj[N:]).any()
    kept = int(graph_ops.unpack_bits(s_p.graph.adj[:N], N).sum())
    assert 0 < kept < N * (N - 1)                  # the prune did work
