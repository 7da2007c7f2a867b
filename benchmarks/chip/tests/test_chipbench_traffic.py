"""The open-loop generator and batch former at a tiny size, against a
hand-computed schedule."""
import numpy as np
import pytest

from benchmarks.chip.traffic import generator as gen


def test_schedule_is_the_same_set_of_gaps_in_a_seeded_order():
    a = gen.arrival_schedule(4.0, 1.0, seed=1)
    b = gen.arrival_schedule(4.0, 1.0, seed=2 ** 40 + 5)
    # 4 requests; gaps are the exponential quantiles at 1/8, 3/8, 5/8, 7/8
    q = -np.log1p(-np.array([0.125, 0.375, 0.625, 0.875]))
    q *= 1.0 / q.sum()
    for s in (a, b):
        assert s[0] == 0.0 and len(s) == 4
        assert np.all(np.diff(s) > 0)
        got = np.sort(np.diff(s))
        assert any(np.allclose(got, np.sort(np.delete(q, k)))
                   for k in range(4))
    assert np.array_equal(a, gen.arrival_schedule(4.0, 1.0, seed=1))


def test_form_batch_orders_by_cohort_and_pads():
    users = np.array([5, 2, 7, 4], np.int32)
    cohort = np.arange(8) % 2
    uids, order = gen.form_batch(np.arange(4), users, cohort, 6)
    assert uids.tolist() == [2, 4, 5, 7, -1, -1]
    assert order.tolist() == [1, 3, 0, 2]


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def sleep(self, s):
        self.t += s


def test_open_loop_latency_counts_from_the_scheduled_arrival():
    # arrivals at 0, 0.1, 0.2, 1.5; a transaction takes 0.5 s; batches of 2
    sched = np.array([0.0, 0.1, 0.2, 1.5])
    users = np.array([0, 1, 2, 3], np.int32)
    clock = FakeClock()
    seen = []

    def serve(uids, tx):
        seen.append((tx, uids.tolist()))
        clock.t += 0.5

    r = gen.run_open_loop(sched, users, np.zeros(4, int), 2, serve,
                          clock=clock, sleep=clock.sleep)
    # t=0: only request 0 has arrived -> served alone, done at 0.5;
    # t=0.5: 1 and 2 -> done at 1.0; idle until 1.5; 3 -> done at 2.0
    assert seen == [(0, [0, -1]), (1, [1, 2]), (2, [3, -1])]
    assert r.latency_s == pytest.approx([0.5, 0.9, 0.8, 0.5])
    assert r.tx_done_s == pytest.approx([0.5, 1.0, 2.0])
    assert r.done_s == pytest.approx(2.0)


def test_the_queue_is_drained_after_the_window():
    sched = np.linspace(0.0, 0.9, 10)
    clock = FakeClock()

    def serve(uids, tx):
        clock.t += 2.0                     # slower than the arrivals

    r = gen.run_open_loop(sched, np.arange(10, dtype=np.int32),
                          np.zeros(10, int), 4, serve, clock=clock,
                          sleep=clock.sleep)
    assert np.all(np.isfinite(r.latency_s)) and len(r.latency_s) == 10
    assert sum(len(b) for b in r.batches) == 10
    assert r.done_s > 0.9
