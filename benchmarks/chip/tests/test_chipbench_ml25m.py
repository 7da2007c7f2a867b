"""The MovieLens-25M cell at sizes a test run holds, on the CPU (Pallas
kernels in interpret mode): its world, its saturated driver and the
``correct`` it computes, the catalog transaction against the plain
reference and the reference engine (one transaction fires the refresh),
and the readers of its per-layer metrics on a small scoped trace."""
import dataclasses
import importlib.util
import json
import pathlib
import types

import numpy as np
import pytest

from benchmarks.chip import run as R
from benchmarks.chip import serve_scopes, trace
from benchmarks.chip import world_movielens as ML
from benchmarks.chip.drivers import open_loop, serve_saturated as S
from benchmarks.chip.traffic import generator as gen

CHIP = pathlib.Path(__file__).resolve().parents[1]
FIX = CHIP / "tests" / "fixtures"


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "m_" + name.replace(".", "_"), CHIP / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def tiny():
    cfg = json.loads((FIX / "ml25m-tiny.json").read_text())
    traffic = cfg.pop("traffic")
    return S.setup(cfg, traffic, seed=2 ** 33 + 9, interpret=True)


def _judge(cell, seconds=2.0):
    win = S.window(cell, seconds)
    e2e, counters, attempted, failed = S.results(cell, win)
    checks = S.check(cell, win)
    correct, _ = R.judge(checks, cell.cfg["limits"], failed)
    return correct, checks, win, counters


# ---------------------------------------------------------------------------
# the world
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", [3, 2 ** 33 + 1])
def test_activities_keep_the_source_floor_total_and_shape(seed):
    a = ML.activities(seed, n_users=162541, total=25000095, floor=20,
                      sigma=1.39)
    assert a.min() >= 20 and a.sum() == 25000095
    assert round(a.mean(), 1) == 153.8
    assert 60 <= np.median(a) <= 80 and a.max() > 20000
    b = ML.activities(seed + 1, n_users=162541, total=25000095, floor=20,
                      sigma=1.39)
    assert sorted(a) == sorted(b) and (a != b).any()   # a seeded order


def test_work_list_covers_every_interaction_once():
    act = ML.activities(5, n_users=1000, total=40000, floor=20, sigma=1.39)
    order, block, step = ML.work_list(act)
    per_block = np.bincount(block)
    for b, steps in enumerate(per_block):
        users = order[b * ML.USER_BLOCK:(b + 1) * ML.USER_BLOCK]
        busiest = act[users[users >= 0]].max()
        assert steps * ML.STEP >= busiest > (steps - 1) * ML.STEP


def test_deployment_is_fixed_and_the_seed_draws_the_traffic(tiny):
    """The catalog, the users' activities and the warm history come from
    ``world_seed`` on every seed; the seed draws the window's requests,
    its transaction keys and the held plain transaction."""
    from benchmarks.chip import world as W
    cfg = tiny.cfg
    assert tiny.word == W.world_word(cfg["world_seed"])
    other = S.setup(cfg, tiny.traffic, seed=tiny.seed + 1, interpret=True)
    assert other.word == tiny.word and other.tx0 != tiny.tx0
    np.testing.assert_array_equal(np.asarray(other.emb),
                                  np.asarray(tiny.emb))
    np.testing.assert_array_equal(other.p_user, tiny.p_user)
    for a, b in ((other.session.state.b, tiny.session.state.b),
                 (other.session.state.occ, tiny.session.state.occ)):
        # the same warm history; the two warm-up batches differ by seed
        a, b = np.asarray(a), np.asarray(b)
        assert np.mean(np.all(a == b, axis=tuple(range(1, a.ndim)))) > 0.5
    assert (S._draw(other, 1)[0] != S._draw(tiny, 1)[0]).any()


def test_warm_history_counts_each_users_interactions(tiny):
    occ = np.asarray(tiny.session.state.occ)
    act = ML.activities(tiny.cfg["world_seed"], n_users=1000, total=40000,
                        floor=20, sigma=1.39)
    # the window has not run: occ is the warm history plus two warm-ups
    assert (occ >= act).all() and occ.sum() == act.sum() + 2 * 128


# ---------------------------------------------------------------------------
# the driver and its check
# ---------------------------------------------------------------------------


def test_saturated_sound_run_is_correct(tiny):
    correct, checks, win, counters = _judge(tiny)
    assert correct, checks
    per = tiny.cfg["refresh_every"] // tiny.cfg["batch"]
    assert counters["transactions"] == per * counters["cycles"]
    assert counters["interactions"] == (counters["cycles"]
                                        * tiny.cfg["refresh_every"])
    assert counters["fold_passes"] >= counters["transactions"]
    assert set(win.held) == {"plain", "refresh"}
    held = win.held["refresh"]
    assert held["index"] == counters["transactions"] - 1
    assert held["tx"] == tiny.tx0 + held["index"]


def test_window_compiles_nothing_and_the_compile_readers_read(
        tiny, monkeypatch):
    """The set-up warms every program the window runs, the held copies
    and the donated transaction included, so the window compiles nothing;
    the compile readers of the paper cell read this cell's log."""
    import time
    from benchmarks.chip import scopes
    from repro.launch import compile_events
    t0 = time.time_ns()
    S.window(tiny, 0.0)
    t1 = time.time_ns()
    done = time.time_ns() + 1
    events = {"devices": {}, "host": [[t0 - t0, t1 - t0, trace.WINDOW_SPAN]],
              "start_ns": t0}
    phases = scopes.compile_phases(
        events, [e for e in compile_events.events() if e[2] < done])
    assert phases["window"]["compiles"] == 0, phases["window"]
    ctx = types.SimpleNamespace(counters={}, workload="ml25m-serve-regions")
    scoped = scopes.Scoped(scope_s={"serve": 1.0}, phases=phases)
    monkeypatch.setattr(scopes, "of", lambda c: scoped)
    got = {name: _reader(name).read(ctx)
           for name in ("compiles_in_window.paper", "setup_trace_s.paper",
                        "setup_compile_s.paper")}
    assert got["compiles_in_window.paper"] == 0
    assert got["setup_trace_s.paper"] >= 0
    assert got["setup_compile_s.paper"] >= 0


def _fault(kind):
    real = S._step

    def broken(cell, uids, tx):
        import jax
        import jax.numpy as jnp
        if kind == "half_batch":
            uids = uids.copy()
            uids[len(uids) // 2:] = -1
        # the transaction donates the state: keep a copy of what it was
        kept = jax.tree.map(jnp.copy, cell.session.state)
        sess, items, m, rmet = real(cell, uids, tx)
        st = sess.state
        if kind == "unchanged_state":
            sess = dataclasses.replace(sess, state=kept)
        if kind == "altered_answer":
            items = (items + 1) % cell.cfg["n_items"]
        if kind == "pruned_edges_kept":      # the refresh's prune undone
            sess = dataclasses.replace(sess, state=st._replace(
                adj=kept.adj))
        if kind == "labels_altered":
            sess = dataclasses.replace(sess, state=st._replace(
                labels=st.labels.at[7].add(1)))
        return sess, items, m, rmet
    return broken


@pytest.mark.parametrize("kind", ["unchanged_state", "half_batch",
                                  "altered_answer", "pruned_edges_kept",
                                  "labels_altered"])
def test_saturated_fault_is_not_correct(tiny, monkeypatch, kind):
    monkeypatch.setattr(S, "_step", _fault(kind))
    correct, checks, _, _ = _judge(tiny)
    assert not correct, checks


def test_saturated_control_reads_above_the_program(tiny):
    _, checks, win, _ = _judge(tiny)
    ctl = S.control(tiny, win)
    assert ctl["fold_rel_err"] > 3 * max(checks["fold_rel_err"], 1e-8)
    assert ctl["fold_rel_err"] > tiny.cfg["limits"]["fold_rel_err"]


def test_catalog_transaction_matches_the_reference_engine(tiny):
    """From the same warm state, the Pallas (interpret) session and the
    ``reference``-engine session serve the same items and fold the same
    statistics, and the second transaction's refresh leaves the same
    graph and labels; the blocked plain reference agrees with both."""
    import jax
    from repro import serve
    from repro.kernels.graph import ops as graph_ops
    cfg = tiny.cfg
    ref_sess = serve.OnlineBandit.create(
        cfg["n_users"], cfg["d"], open_loop._hyper(cfg), policy="distclub",
        refresh_every=cfg["refresh_every"], backend="reference",
        precision=cfg["precision"])
    # two batches short of the budget: the second transaction refreshes
    st = tiny.session.state._replace(since_refresh=jax.numpy.asarray(
        cfg["refresh_every"] - 2 * cfg["batch"], jax.numpy.int32))
    ref_cell = dataclasses.replace(
        tiny, session=dataclasses.replace(ref_sess, state=st))
    pal_cell = dataclasses.replace(
        tiny, session=dataclasses.replace(tiny.session, state=st))
    uids = S._draw(dataclasses.replace(
        tiny, rng=np.random.default_rng(11)), 2)
    for t, u in enumerate(uids):
        before = pal_cell.session.state
        outs = []
        for cell in (pal_cell, ref_cell):
            sess, items, _, rmet = open_loop._step(cell, u, 7000 + t)
            cell.session = sess
            outs.append((sess.state, np.asarray(items)))
        (sp, ip), (sr, ir) = outs
        np.testing.assert_array_equal(ip, ir)
        np.testing.assert_allclose(np.asarray(sp.Minv), np.asarray(sr.Minv),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(sp.b), np.asarray(sr.b),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_array_equal(np.asarray(sp.occ), np.asarray(sr.occ))
        np.testing.assert_array_equal(np.asarray(sp.adj), np.asarray(sr.adj))
        np.testing.assert_array_equal(np.asarray(sp.labels),
                                      np.asarray(sr.labels))
        held = {"tx": 7000 + t, "uids": u, "before": S._rows(before),
                "after": sp}
        got = open_loop.check_tx(pal_cell, held, ip)
        assert got["item_gap"] <= cfg["limits"]["item_gap"], got
        assert got["fold_rel_err"] <= cfg["limits"]["fold_rel_err"], got
    assert int(sp.since_refresh) == 0                 # the refresh fired
    assert sp.adj.shape == graph_ops.stored_shape(cfg["n_users"],
                                                  cfg["n_users"])
    got = S.check_refresh(pal_cell, {"graph": S.Graph(before.adj),
                                     "after": sp})
    assert got["prune_margin"] == 0 and got["cc_mismatch"] == 0, got
    jax.block_until_ready(sp)


def test_blocked_reference_takes_a_user_count_no_block_divides():
    """``serve_blocked`` on a stored (padded) graph of 1,000 users equals
    ``serve.py``'s fixed-block reference on the same graph cut to 1,024
    rows and columns of which the last 24 are padding."""
    import jax.numpy as jnp
    from benchmarks.chip.reference import serve as ref
    from benchmarks.chip.reference import serve_blocked as blocked
    from repro.kernels.graph import ops as graph_ops
    n, d = 1000, 6
    rng = np.random.default_rng(4)
    Minv = jnp.asarray(np.broadcast_to(np.eye(d, dtype=np.float32),
                                       (n, d, d)))
    b = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    occ = jnp.asarray(rng.integers(1, 50, n).astype(np.int32))
    dense = rng.random((n, n)) < 0.003
    dense = np.triu(dense, 1) | np.triu(dense, 1).T
    stored = np.zeros(graph_ops.stored_shape(n, n), np.uint32)
    stored[:n, :32] = np.asarray(graph_ops.pack_bits(jnp.asarray(dense)))
    stored = jnp.asarray(stored)
    labels = blocked.components(stored, n)
    want = ref.components(stored, 1024, rows=512)[:n]
    np.testing.assert_array_equal(labels, want)
    full = jnp.asarray(np.where(np.arange(1024)[:, None] < n,
                                np.asarray(stored), 0))
    count, _ = blocked.prune_flips(Minv, b, occ, 1.6, full, full)
    want, _ = ref.prune_flips(
        jnp.concatenate([Minv, jnp.broadcast_to(jnp.eye(d), (24, d, d))]),
        jnp.concatenate([b, jnp.zeros((24, d))]),
        jnp.concatenate([occ, jnp.zeros(24, jnp.int32)]), 1.6, full, full)
    assert count == want > 0


# ---------------------------------------------------------------------------
# the readers
# ---------------------------------------------------------------------------


@pytest.fixture
def serve_ctx(monkeypatch):
    events = json.loads((FIX / "serve_scoped_trace.json").read_text())
    scope_s, refresh_ops = serve_scopes.reduce(events)
    monkeypatch.setattr(serve_scopes, "of", lambda ctx: serve_scopes.scopes
                        .Scoped(scope_s=scope_s, phases=None))
    plain = dict(events, devices={k: [o[:3] for o in v]
                                  for k, v in events["devices"].items()})
    return types.SimpleNamespace(
        reduced=trace.reduce(plain), cfg={}, workload="ml25m-serve-regions",
        counters={"transactions": 2, "refreshes": 1, "fold_passes": 5},
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        refresh_ops=refresh_ops, scope_s=scope_s)


def test_serve_scope_paths_and_refresh_ops(serve_ctx):
    s = serve_ctx.scope_s
    assert s == pytest.approx({
        "serve/gather_score": 100e-9, "serve/tile_bounds": 50e-9,
        "serve/retrieve": 230e-9, "serve/choose": 40e-9,
        "serve/env_rewards": 10e-9, "serve/fold": 20e-9,
        "serve": 15e-9,                     # the cond, less its children
        "serve/refresh/stage2/prune": 200e-9,
        "serve/refresh/stage2/cc": 50e-9,
        "serve/refresh/stage2/gram_inverse": 20e-9,
        "serve/refresh/stage2/cluster_inverse": 15e-9, "": 40e-9})
    assert sum(s.values()) == pytest.approx(serve_ctx.reduced.busy_s)
    assert serve_ctx.refresh_ops == [
        ["graph_prune.9", pytest.approx(200e-9)],
        ["cc_hop.10", pytest.approx(50e-9)],
        ["spd_inverse.12", pytest.approx(20e-9)],
        ["spd_inverse.13", pytest.approx(15e-9)]]


@pytest.mark.parametrize("name,want", [
    ("retrieve_ms.ml25m", 1e3 * 230e-9 / 2),
    ("tile_bounds_ms.ml25m", 1e3 * 50e-9 / 2),
    ("gather_score_ms.ml25m", 1e3 * 100e-9 / 2),
    ("refresh_all_ms.ml25m", 1e3 * 285e-9 / 1),
    ("refresh_inverse_ms.ml25m", 1e3 * 35e-9 / 1),
    ("unscoped_ms.ml25m", 1e3 * 40e-9 / 2),
    ("fold_passes.ml25m", 2.5),
])
def test_serve_readers_on_a_scoped_trace(serve_ctx, name, want):
    assert _reader(name).read(serve_ctx) == pytest.approx(want)


def test_serve_readers_have_nothing_to_read_without_scopes(monkeypatch):
    monkeypatch.setattr(serve_scopes, "of", lambda ctx: serve_scopes.scopes
                        .Scoped(scope_s={"": 1.0}, phases=None))
    ctx = types.SimpleNamespace(counters={"transactions": 2, "refreshes": 1})
    for name in ("retrieve_ms.ml25m", "tile_bounds_ms.ml25m",
                 "gather_score_ms.ml25m", "refresh_all_ms.ml25m",
                 "refresh_inverse_ms.ml25m", "unscoped_ms.ml25m"):
        assert _reader(name).read(ctx) is None
    assert _reader("fold_passes.ml25m").read(ctx) is None


def test_retrieve_roofline_counts_only_the_visits_made():
    """With nine tenths of the visits skipped, the share of the visits
    made stays at or under 100% where the whole-catalog count
    (``retrieval_roofline.serve``) would read far above it."""
    m = _reader("retrieve_roofline.ml25m")
    pk = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    tx, T, blocks = 100, 123, 8
    c = {"transactions": tx, "user_blocks_per_tx": blocks,
         "tiles_total": tx * T * blocks, "tiles_skipped": tx * T * blocks
         * 9 // 10, "block_users": 128, "tile_items": 512, "d": 19}
    visits = c["tiles_total"] - c["tiles_skipped"]
    assert m.flops(visits, 128, 512, 19) == 2 * visits * 128 * 512 * 380
    need = m.least_s(c, pk)
    ctx = types.SimpleNamespace(
        reduced=types.SimpleNamespace(kernel_s={"topk_pruned": need}),
        counters=c, peaks=pk)
    assert m.read(ctx) == pytest.approx(100.0)      # at its roofline
    old = _reader("retrieval_roofline.serve")
    ctx.counters = dict(c, valid_per_tx=[1024] * tx)
    ctx.cfg = {"n_items": 62423, "d": 19}
    assert old.read(ctx) > 500.0
    ctx.reduced = types.SimpleNamespace(kernel_s={})
    assert m.read(ctx) is None
