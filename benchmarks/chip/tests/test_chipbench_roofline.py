"""The roofline work and byte functions at catalog serving sizes (65,536
users, 2^20 items, d=32, batches of 1,024), and the table of peaks."""
import importlib.util
import pathlib

import pytest

from benchmarks.chip import peaks

HERE = pathlib.Path(__file__).resolve().parents[1]


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "m_" + name.replace(".", "_"), HERE / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_retrieval_work_and_bytes_at_catalog_65k():
    m = _reader("retrieval_roofline.serve")
    B, N, d = 1024, 2 ** 20, 32
    assert m.flops(B, N, d) == 2 * 1024 * 2 ** 20 * (32 + 1024)
    assert m.bytes_moved(B, N, d) == 4 * 2 ** 20 * 32 + 4 * 1024 * 1024
    v5e = peaks.peaks_for("TPU v5 lite")
    # compute-bound: 2.27e12 flop at 197e12/s is 11.5 ms; bytes take 0.17 ms
    assert m.least_s(B, N, d, v5e) == pytest.approx(
        2 * 1024 * 2 ** 20 * 1056 / 197e12)
    assert m.least_s(B, N, d, v5e) == pytest.approx(11.51e-3, rel=1e-3)


def test_roofline_share_reads_the_kernels_and_valid_rows_only():
    import types
    m = _reader("retrieval_roofline.serve")
    pk = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    red = types.SimpleNamespace(kernel_s={"topk_pruned": 2.0, "topk": 2.0})
    ctx = types.SimpleNamespace(
        reduced=red, peaks=pk, counters={"valid_per_tx": [100, 50]},
        cfg={"n_items": 1000, "d": 4})
    need = sum(max(2 * B * 1000 * 20 / 1e12,
                   (4 * 1000 * 4 + 4 * B * 16) / 1e9) for B in (100, 50))
    assert m.read(ctx) == pytest.approx(100 * need / 4.0)
    ctx.reduced = types.SimpleNamespace(kernel_s={})
    assert m.read(ctx) is None                 # nothing to read: no number


def test_peaks_are_keyed_by_device_kind():
    v5e = peaks.peaks_for("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in v5e["source"]


def test_an_unknown_device_kind_is_an_error():
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for("cpu")
