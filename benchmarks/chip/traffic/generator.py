"""The one traffic generator: open-loop arrival schedules, user draws and
the greedy batch former.  A traffic mix is a data file beside this one
(``<name>.json``); this module reads its parameters and nothing else.

Every seed gets the same set of inter-arrival gaps — the exponential
distribution's quantiles at the mix's rate, scaled to fill the window
exactly — in a seed-drawn order, so two seeds offer the same work at the
same mean rate and differ only in when the bursts fall.  Latency is
counted from each request's scheduled arrival, so a stall delays every
request scheduled behind it; after the window closes the queue is
drained, so every scheduled request is timed.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib
import time

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent


def load(name: str) -> dict:
    path = HERE / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {name!r} ({path})")
    return json.loads(path.read_text())


def rng(seed: int, stream: int) -> np.random.Generator:
    """An independent numpy stream per purpose, from any whole seed."""
    return np.random.default_rng([int(seed) & (2**63 - 1), stream])


def arrival_schedule(rate_per_s: float, seconds: float, seed: int):
    """Arrival offsets in ``[0, seconds)``, ascending: ``round(rate *
    seconds)`` requests whose gaps are exponential quantiles in a
    seed-drawn order."""
    n = max(1, int(round(rate_per_s * seconds)))
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q)
    gaps *= seconds / gaps.sum()
    gaps = gaps[rng(seed, 1).permutation(n)]
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])


def draw_users(n_users: int, count: int, seed: int):
    """Uniform user ids, one per request."""
    return rng(seed, 2).integers(0, n_users, count, dtype=np.int64).astype(
        np.int32)


def form_batch(req_idx, users, cohort, batch: int):
    """Order the taken requests by cohort (stable, so arrival order within a
    cohort) and pad to ``batch`` with uid -1.  Returns ``(uids [batch]
    int32, req_idx in batch order)``."""
    u = users[req_idx]
    order = np.argsort(cohort[u], kind="stable")
    out = np.full(batch, -1, np.int32)
    out[:len(u)] = u[order]
    return out, req_idx[order]


@dataclasses.dataclass
class OpenLoopResult:
    latency_s: np.ndarray        # [N] completion - scheduled arrival
    done_s: float                # last completion, from the window start
    batches: list                # [(req_idx in batch order, tx index)]
    tx_done_s: list              # completion offset of each transaction
    sleep_late_s: float          # worst oversleep of the generator


def run_open_loop(schedule, users, cohort, batch: int, serve, *,
                  clock=time.perf_counter, sleep=time.sleep,
                  span=None) -> OpenLoopResult:
    """Serve ``schedule`` greedily: whenever the server is free, take up to
    ``batch`` arrived requests in arrival order, form the batch, and call
    ``serve(uids, tx)``, which returns once the transaction's results are
    ready.  With nothing arrived, sleep until the next arrival.  ``span``
    (a context-manager factory taking a name) marks host spans for the
    trace."""
    span = span or _no_span
    n = len(schedule)
    lat = np.empty(n)
    batches, tx_done = [], []
    nxt, tx, late = 0, 0, 0.0
    t0 = clock()
    while nxt < n:
        now = clock() - t0
        if schedule[nxt] > now:
            with span("bench.idle"):
                sleep(schedule[nxt] - now)
            late = max(late, clock() - t0 - schedule[nxt])
            continue
        with span("bench.form"):
            arrived = int(np.searchsorted(schedule, now, side="right"))
            take = np.arange(nxt, min(arrived, nxt + batch))
            uids, order = form_batch(take, users, cohort, batch)
        serve(uids, tx)
        done = clock() - t0
        lat[take] = done - schedule[take]
        batches.append(order)
        tx_done.append(done)
        nxt = take[-1] + 1
        tx += 1
    return OpenLoopResult(latency_s=lat, done_s=tx_done[-1] if tx_done
                          else 0.0, batches=batches, tx_done_s=tx_done,
                          sleep_late_s=late)


class _no_span:
    def __init__(self, name):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False
