"""The trace reducer on a small recorded trace: busy as the union of
device intervals, kernel time by kernel name, idle gaps by host span."""
import json
import pathlib

import pytest

from benchmarks.chip import trace

FIX = pathlib.Path(__file__).resolve().parent / "fixtures" / "small_trace.json"


@pytest.fixture(scope="module")
def reduced():
    return trace.reduce(json.loads(FIX.read_text()))


def test_busy_is_the_union_of_device_intervals_inside_the_window(reduced):
    # [0, 400) + [500, 900) + [1000, 1100); the nested ops add nothing and
    # the op after the window is left out
    assert reduced.busy_s == pytest.approx(900e-9)
    assert reduced.window_s == pytest.approx(1200e-9)


def test_kernel_time_is_attributed_by_instruction_name(reduced):
    assert reduced.kernel_s == pytest.approx(
        {"topk": 300e-9, "cc_hop": 100e-9, "choose": 100e-9})
    assert "topk_pruned" not in reduced.kernel_s     # outside the window


def test_idle_gaps_carry_the_overlapping_host_span(reduced):
    assert [g[0] for g in reduced.idle_gaps] == ["bench.dispatch",
                                                 "bench.idle", "bench.wait"]
    assert [g[1] for g in reduced.idle_gaps] == pytest.approx([100e-9] * 3)


def test_device_ops_rank_self_time(reduced):
    ops = dict(reduced.device_ops)
    assert ops["topk.3"] == pytest.approx(300e-9)
    assert ops["while.2"] == pytest.approx(200e-9)   # 400 less its children
    assert reduced.device_ops[0][0] == "topk.3"


@pytest.mark.parametrize("name,base", [
    ("%topk_pruned.12 = (f32[1]) custom-call()", "topk_pruned"),
    ("%rank1_update_inv = f32[1] custom-call()", "rank1_update_inv"),
    ("%fusion.3 = f32[2] fusion()", "fusion"),
])
def test_op_base(name, base):
    assert trace.op_base(name) == base


def test_a_trace_without_device_operations_is_refused():
    with pytest.raises(ValueError):
        trace.reduce({"devices": {"/device:TPU:0": []}, "host": []})
