"""Every cell of BENCHMARK.json resolves its configuration, traffic and
metric files by name, and the harness refuses to run without a TPU."""
import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

CHIP = pathlib.Path(__file__).resolve().parents[1]
ROOT = CHIP.parents[1]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda c: c["name"])
def test_cell_resolves_its_files(cell):
    from benchmarks.chip import run
    _, entry, cfg, traffic = run.load_cell(cell["name"])
    assert entry["chips"] == 1
    assert cfg["name"] == cell["config"]
    assert (CHIP / "drivers" / f"{traffic['kind']}.py").is_file()
    assert (CHIP / cfg["reference"]).is_file()
    assert cfg["limits"]
    for kind in ("end_to_end", "per_layer"):
        names = [m["name"] for m in run.cell_metrics(BENCH, cell["name"],
                                                     kind)]
        assert names, kind
        if kind == "per_layer":
            for n in names:
                assert callable(run.load_reader(n))


def test_configs_match_their_files():
    for c in BENCH["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        assert cfg["reduced"] == c["reduced"]
        assert c["file"].startswith(BENCH["paths"][0] + "/")


def test_every_per_layer_metric_moves_a_reported_end_to_end_metric():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        target = e2e[m["moves"]]
        for w in m["workloads"]:
            assert w in target.get("workloads", [w])


def _run(cwd, env):
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "paper-distclub", "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=240)


def test_no_tpu_means_no_result_and_a_nonzero_exit(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = _run(ROOT, env)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr
    # the benchmark's own files alone, without the program
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(CHIP, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path, env)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
