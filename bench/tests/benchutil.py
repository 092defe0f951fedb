"""Helpers shared by the benchmark's CPU tests: a scratch copy of the
benchmark with tiny cells added by files and entries alone."""
import json
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_IMAGE = {"kind": "image", "features": 48, "train": 512, "test": 128,
              "classes": 4, "template_rank": 4, "noise": 0.6}


def tiny_copy(tmp: Path, limits_from="cifar10.seq-train") -> Path:
    """Copy ``bench/`` and ``BENCHMARK.json`` to ``tmp`` and add, by new
    files and new entries only, a tiny configuration with one cell per
    traffic mix: ``tiny.seq-train`` and ``tiny.wasap4-train``."""
    shutil.copytree(ROOT / "bench", tmp / "bench",
                    ignore=shutil.ignore_patterns(".out", "__pycache__"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfg = json.loads((ROOT / "bench/configs/set-mlp-cifar10.json").read_text())
    cfg.update(name="tiny", layer_dims=[48, 64, 32, 4], epsilon=6,
               nnz=[672, 576, 144], batch_size=16, dataset=TINY_IMAGE)
    (tmp / "bench/configs/tiny.json").write_text(json.dumps(cfg))
    spec["configs"].append({"name": "tiny", "source": "test", "reduced": [],
                            "file": "bench/configs/tiny.json", "why": "test"})
    limits = (ROOT / "bench/limits" / f"{limits_from}.json").read_text()
    for traffic in ("seq-train", "wasap4-train"):
        name = f"tiny.{traffic}"
        spec["workloads"].append({"name": name, "config": "tiny",
                                  "traffic": traffic, "chips": 1, "why": "test"})
        (tmp / "bench/limits" / f"{name}.json").write_text(limits)
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp / "bench"


def run_tiny(bench: Path, workload: str, seconds: float = 0.5, seed: int = 3):
    import time

    from bench import harness

    spec = json.loads((bench.parent / "BENCHMARK.json").read_text())
    return harness.run_cell(workload, seed, seconds, False, time.perf_counter(),
                            require_chip=False, spec=spec, bench=bench)
