"""``correct`` at a size a test run holds, on the CPU (Pallas kernels in
interpret mode): sound runs pass; the control (the reference one precision
step below, in the program's place) reads well above the program; and a
run whose timed path is broken underneath comes out not correct, once for
each fault a cell can have (its state returned unchanged, half the batch
left out, an answer altered where it is produced).  A one-chip cell has no
exchange between chips to leave out.
"""
import json
import pathlib

import pytest

from benchmarks.chip import run as R
from benchmarks.chip.drivers import closed_epochs, open_loop

FIXTURES = pathlib.Path(__file__).resolve().parent / "fixtures"
PAPER = {"n_users": 1024}
# the number that has to catch each planted stage-2 fault on its own
PAPER_STAGE2_CATCH = {"pruned_edges_kept": "prune_margin",
                      "labels_altered": "cc_mismatch",
                      "cluster_stats_altered": "cluster_size_mismatch"}


def _cell(workload, over):
    _, _, cfg, traffic = R.load_cell(workload)
    cfg.update(over)
    return cfg, traffic


@pytest.fixture(scope="module")
def serve_cell():
    """The open-loop serve driver on a tiny catalog: no cell runs it on
    the chip yet, and these tests keep its check honest meanwhile."""
    cfg = json.loads((FIXTURES / "catalog-tiny.json").read_text())
    traffic = cfg.pop("traffic")
    return open_loop.setup(cfg, traffic, seed=2 ** 33 + 7, interpret=True)


@pytest.fixture(scope="module")
def paper_cell():
    cfg, traffic = _cell("paper-distclub", PAPER)
    return closed_epochs.setup(cfg, traffic, seed=5, interpret=True)


def _judge(driver, cell, seconds=3.0):
    win = driver.window(cell, seconds)
    _, _, _, failed = driver.results(cell, win)
    checks = driver.check(cell, win)
    correct, _ = R.judge(checks, cell.cfg["limits"], failed)
    return correct, checks, win


def _serve_fault(kind):
    real = open_loop._step

    def broken(cell, uids, tx):
        if kind == "half_batch":
            uids = uids.copy()
            uids[len(uids) // 2:] = -1
        sess, items, m, rmet = real(cell, uids, tx)
        if kind == "unchanged_state":
            sess = cell.session
        if kind == "altered_answer":
            items = (items + 1) % cell.cfg["n_items"]
        return sess, items, m, rmet
    return broken


def _paper_fault(kind):
    real = closed_epochs._call

    def broken(cell, c):
        st, m, ncl = real(cell, c)
        lin, graph, stats = st.lin, st.graph, st.clusters
        n = lin.b.shape[0]
        if kind == "unchanged_state":
            lin = lin._replace(b=lin.b * 0, occ=lin.occ * 0)
        if kind == "half_batch":
            lin = lin._replace(b=lin.b.at[n // 2:].set(0.0),
                               occ=lin.occ.at[n // 2:].set(0))
        if kind == "altered_answer":
            m = m._replace(reward=1.0 - m.reward)
        if kind == "pruned_edges_kept":     # rows whose prune was skipped
            graph = graph._replace(adj=graph.adj.at[:n // 16].set(
                0xFFFFFFFF))
        if kind == "labels_altered":        # a component split in two
            graph = graph._replace(labels=graph.labels.at[n // 2].set(
                graph.labels[n // 2] + 1))
        if kind == "cluster_stats_altered":
            stats = stats._replace(size=stats.size.at[0].add(1))
        return st._replace(lin=lin, graph=graph, clusters=stats), m, ncl
    return broken


def test_serve_sound_run_is_correct(serve_cell):
    correct, checks, win = _judge(open_loop, serve_cell)
    assert win.held, "the window held no refresh transaction"
    assert correct, checks


@pytest.mark.parametrize("kind", ["unchanged_state", "half_batch",
                                  "altered_answer"])
def test_serve_fault_is_not_correct(serve_cell, monkeypatch, kind):
    monkeypatch.setattr(open_loop, "_step", _serve_fault(kind))
    correct, checks, _ = _judge(open_loop, serve_cell)
    assert not correct, checks


def test_serve_control_reads_above_the_program(serve_cell):
    _, checks, win = _judge(open_loop, serve_cell)
    ctl = open_loop.control(serve_cell, win)
    assert ctl["fold_rel_err"] > 3 * max(checks["fold_rel_err"], 1e-8)
    assert ctl["fold_rel_err"] > serve_cell.cfg["limits"]["fold_rel_err"]


def test_paper_sound_run_is_correct(paper_cell):
    correct, checks, _ = _judge(closed_epochs, paper_cell, 1.0)
    assert correct, checks


@pytest.mark.parametrize("kind", ["unchanged_state", "half_batch",
                                  "altered_answer", "pruned_edges_kept",
                                  "labels_altered", "cluster_stats_altered"])
def test_paper_fault_is_not_correct(paper_cell, monkeypatch, kind):
    monkeypatch.setattr(closed_epochs, "_call", _paper_fault(kind))
    correct, checks, _ = _judge(closed_epochs, paper_cell, 1.0)
    assert not correct, checks
    number = PAPER_STAGE2_CATCH.get(kind)
    if number:
        assert checks[number] > paper_cell.cfg["limits"][number], checks


def test_paper_unrewarded_fork_is_not_a_matched_user():
    """A pick that differs and is unrewarded on both sides leaves ``occ``
    and ``b`` equal and moves ``Minv``: that user's stage-2 statistics are
    not the reference's, so its pairs are not judged."""
    import numpy as np
    from types import SimpleNamespace
    n, d = 4, 3
    rng = np.random.default_rng(0)
    eye = np.broadcast_to(np.eye(d, dtype=np.float32), (n, d, d)).copy()
    x0, x1 = rng.normal(size=d), rng.normal(size=d)

    def fold(M, x):
        Mx = M @ x
        return M - np.outer(Mx, Mx) / (1.0 + x @ Mx)

    b = rng.normal(size=(n, d)).astype(np.float32)
    occ = np.full(n, 5)
    ref = SimpleNamespace(Minv=eye.copy(), b=b, occ=occ)
    side = SimpleNamespace(Minv=eye.copy(), b=b.copy(), occ=occ.copy())
    ref.Minv[2] = fold(ref.Minv[2], x0)
    side.Minv[2] = fold(side.Minv[2], x1)
    side.Minv[1] += 1e-7                       # rounding only
    agree = closed_epochs._agree(side, ref)
    assert agree.tolist() == [True, True, False, True]


def test_paper_control_reads_above_the_program(paper_cell):
    _, checks, win = _judge(closed_epochs, paper_cell, 1.0)
    ctl = closed_epochs.control(paper_cell, win)
    assert ctl["stage1_gap_per_kuser"] > 3 * max(
        checks["stage1_gap_per_kuser"], 1.0)
    assert (ctl["stage1_gap_per_kuser"]
            > paper_cell.cfg["limits"]["stage1_gap_per_kuser"])
