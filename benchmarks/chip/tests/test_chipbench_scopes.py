"""Device time by named scope and compile events by phase: the scope
reducer on a small trace whose device operations carry ``op_name``s, and
the program's compile log split at the window."""
import gzip
import json
import pathlib
import time

import jax
import jax.numpy as jnp
import pytest

from benchmarks.chip import scopes, trace

FIX = pathlib.Path(__file__).resolve().parent / "fixtures"


@pytest.fixture(scope="module")
def scoped():
    return json.loads((FIX / "scoped_trace.json").read_text())


@pytest.mark.parametrize("op_name,path", [
    ("jit(_run)/epoch/while/body/closed_call/stage2/cluster_inverse/"
     "jit(inv)/jit(solve)/vmap()/lu", "epoch/stage2/cluster_inverse"),
    ("jit(_run)/init/broadcast_in_dim", "init"),
    ("jit(_run)/epoch/while/body/closed_call/stage1/while/body/closed_call/"
     "choose/jit(choose_pallas)/choose/pallas_call", "epoch/stage1/choose"),
    ("jit(_run)/while/body/closed_call/while/body/closed_call/"
     "jit(choose_pallas)/choose/pallas_call", ""),
    ("jit(solve)/vmap()/lu", ""),
    ("", ""),
    (None, ""),
])
def test_scope_path_keeps_the_scope_components_in_order(op_name, path):
    assert scopes.scope_path(op_name) == path


def test_op_names_come_from_the_device_events_of_the_json_export(tmp_path):
    events = [
        {"ph": "M", "pid": 3, "name": "process_name",
         "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "pid": 7, "name": "process_name",
         "args": {"name": "/host:CPU"}},
        {"ph": "X", "pid": 3, "tid": 3, "name": "custom-call.116",
         "args": {"tf_op": "jit(_run)/epoch/while/body/closed_call/stage2/"
                           "gram_inverse/jit(inv)/jit(solve)/vmap()/lu:"}},
        {"ph": "X", "pid": 3, "tid": 3, "name": "copy.7", "args": {}},
        {"ph": "X", "pid": 7, "tid": 1, "name": "fusion.1",
         "args": {"tf_op": "host"}},
    ]
    path = tmp_path / "t.trace.json.gz"
    with gzip.open(path, "wt") as f:
        json.dump({"traceEvents": events}, f)
    names = scopes.op_names(str(path))
    assert names == {"custom-call.116": "jit(_run)/epoch/while/body/"
                     "closed_call/stage2/gram_inverse/jit(inv)/jit(solve)/"
                     "vmap()/lu"}
    assert scopes.scope_path(names["custom-call.116"]) == \
        "epoch/stage2/gram_inverse"
    assert scopes.op_names(str(tmp_path / "missing.trace.json.gz")) == {}


def test_self_time_by_innermost_scope(scoped):
    s = scopes.reduce_scopes(scoped)
    assert s == pytest.approx({
        "init": 100e-9,
        "epoch": 100e-9,                  # 400 less its three children
        "epoch/stage1/choose": 100e-9,
        "epoch/stage1/score": 50e-9,
        "epoch/stage2/cc": 150e-9,        # the inner while and its hop
        "": 80e-9,                        # the copy with no op_name
        "refresh_gram": 60e-9,
    })
    r = scopes.Scoped(scope_s=s, phases=None)
    assert r.named
    assert r.inner_s("cc") == pytest.approx(150e-9)
    assert r.inner_s("choose", "score") == pytest.approx(150e-9)
    assert r.under_s("stage2") == pytest.approx(150e-9)
    assert r.under_s("epoch") == pytest.approx(400e-9)


def test_a_while_and_its_children_add_up_to_busy_time(scoped):
    plain = dict(scoped, devices={k: [o[:3] for o in v]
                                  for k, v in scoped["devices"].items()})
    busy = trace.reduce(plain).busy_s
    assert busy == pytest.approx(640e-9)
    assert sum(scopes.reduce_scopes(scoped).values()) == pytest.approx(busy)


def test_three_field_events_fall_under_the_empty_path():
    events = json.loads((FIX / "small_trace.json").read_text())
    assert scopes.reduce_scopes(events) == pytest.approx({"": 900e-9})
    assert not scopes.Scoped(scopes.reduce_scopes(events), None).named
    assert trace.reduce(events).busy_s == pytest.approx(900e-9)


def _phases(t0, lo, hi):
    from repro.launch import compile_events
    events = {"devices": {"/device:TPU:0": [[lo - t0, 1.0, "%f.1 = f()"]]},
              "host": [[lo - t0, hi - lo, trace.WINDOW_SPAN]],
              "start_ns": t0}
    since = [e for e in compile_events.events() if e[2] >= t0]
    return scopes.compile_phases(events, since)


def test_compiles_land_in_their_own_phase():
    from repro.launch import compile_events
    assert compile_events.events() is not None
    x = jnp.arange(4.0)
    t0 = time.time_ns()
    f = jax.jit(lambda v: v * 3.0 + 1.0)
    f(x).block_until_ready()                 # set-up: a fresh program
    lo = time.time_ns()
    g = jax.jit(lambda v: v * 5.0 - 2.0)
    g(x).block_until_ready()                 # window: a fresh program
    f(x).block_until_ready()                 # window: the in-memory cache
    hi = time.time_ns()
    jax.jit(lambda v: v - 7.0)(x).block_until_ready()   # after the window
    p = _phases(t0, lo, hi)
    assert p["setup"]["compiles"] == 1
    assert p["setup"]["trace_s"] > 0 and p["setup"]["compile_s"] > 0
    assert p["window"]["compiles"] == 1
    assert p["window"]["lowerings"] == 1
    assert p["after"]["compiles"] == 1


def test_a_call_from_the_in_memory_cache_counts_nothing():
    x = jnp.arange(4.0)
    f = jax.jit(lambda v: v * 11.0)
    f(x).block_until_ready()
    t0 = lo = time.time_ns()
    f(x).block_until_ready()
    hi = time.time_ns()
    p = _phases(t0, lo, hi)
    assert p["window"] == dict(p["window"], compiles=0, lowerings=0,
                               traces=0, cache_reads=0)
    assert p["window"]["trace_s"] == p["window"]["compile_s"] == 0
