"""The harness: every file is found by name, a cell added by files alone
runs, ``BENCHMARK.json`` keeps to its contract, and no chip means no
result."""
import json
import os
import re
import shutil
import subprocess
import sys

import benchutil
import pytest

from bench import check, harness

ROOT = benchutil.ROOT
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_finds_its_files(cell):
    c = harness.resolve(SPEC, cell)
    assert c["config"]["name"] == c["cell"]["config"]
    assert c["traffic"]["job"] in ("sequential", "local_sgd")
    assert set(c["limits"]) == set(check.NAMES)
    assert all(c["limits"][k] == 0 for k in ("nnz", "positions", "eval_acc"))
    for path in c["readers"].values():
        assert callable(harness.load_reader(path))
    names = {m["name"] for m in c["end_to_end"]}
    assert {"setup_s", "train_samples_per_s", "peak_hbm_bytes"} <= names
    assert c["per_layer"]


def test_spec_keeps_to_the_contract():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"] and 1 <= SPEC["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for x in SPEC[k]]
    assert len(names) == len(set(names)) and all(NAME.match(n) for n in names)
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/") and (ROOT / c["file"]).is_file()
        config = json.loads((ROOT / c["file"]).read_text())
        assert config["reduced"] == c["reduced"]
        assert config["reference"].startswith("bench/")
        assert (ROOT / config["reference"]).is_file()
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    assert sum(w["chips"] == 4 for w in SPEC["workloads"]) <= max(
        1, len(SPEC["workloads"]) // 2)
    for m in SPEC["end_to_end"]:
        assert UNIT.match(m["unit"]) and 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e
    for m in SPEC["per_layer"]:
        assert UNIT.match(m["unit"]) and m["moves"] in e2e
        assert (ROOT / "bench/metrics" / f"{m['name']}.py").is_file()
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"


def test_a_cell_added_by_files_alone_runs(tmp_path):
    bench = benchutil.tiny_copy(tmp_path)
    result = benchutil.run_tiny(bench, "tiny.seq-train")
    assert result["correct"] is True and result["attempted"] >= 1
    assert list(result)[-1] == "compared"
    assert set(result["metrics"]) == {"train_samples_per_s", "peak_hbm_bytes",
                                      "setup_s"}
    assert result["metrics"]["train_samples_per_s"]["value"] > 0


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "cifar10.seq-train",
         "--seed", "1", "--seconds", "1"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_chip_means_no_result():
    out = _run(ROOT)
    assert out.returncode != 0 and out.stdout.strip() == ""
    assert "accelerator" in out.stderr


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".out", "__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    out = _run(tmp_path, {"PYTHONPATH": ""})
    assert out.returncode != 0 and out.stdout.strip() == ""
