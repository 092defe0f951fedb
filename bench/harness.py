"""The benchmark's driver: finds a cell's files by name, checks for the
chips, runs the cell's job, decides ``correct``, reads the metrics and
prints the result line.

Everything that belongs to one configuration, traffic mix, cell or metric
sits in a file of its own, found by the name ``BENCHMARK.json`` gives:

* ``configs/<config>.json``: the configuration as it is run (the path is
  the ``file`` of its ``BENCHMARK.json`` entry);
* ``traffic/<traffic>.json``: the job that ``jobs.run_job`` drives;
* ``limits/<cell>.json``: the limits of the numbers ``check`` compares;
* ``metrics/<metric>.py``: a ``read(ctx)`` that returns the per-layer
  metric, or ``None`` where the trace holds nothing to read.
"""
from __future__ import annotations

import functools
import importlib.util
import json
import math
import shutil
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
OUT = BENCH / ".out"


def note(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def load_spec(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def resolve(spec: dict, workload: str, bench: Path = BENCH) -> dict:
    """The cell ``workload`` with its configuration, traffic, limits and the
    per-layer and end-to-end metrics it reports, all found by name."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    conf = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    root = bench.parent
    config = json.loads((root / conf["file"]).read_text())
    # the configuration's reference, found in the checkout that holds the cell
    config["reference"] = str(root / config["reference"])

    def applies(metric):
        return workload in metric.get("workloads", [workload])

    return {
        "cell": cell,
        "config": config,
        "traffic": json.loads((bench / "traffic" / f"{cell['traffic']}.json").read_text()),
        "limits": json.loads((bench / "limits" / f"{workload}.json").read_text()),
        "end_to_end": [m for m in spec["end_to_end"] if applies(m)],
        "per_layer": [m for m in spec["per_layer"] if applies(m)],
        "readers": {m["name"]: bench / "metrics" / f"{m['name']}.py"
                    for m in spec["per_layer"] if applies(m)},
    }


@functools.cache
def load_module(path: Path):
    """The Python file at ``path``, loaded by path once per process."""
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(path: Path):
    return load_module(path).read


class MetricContext:
    """What a per-layer reader may read: the reduced trace (``red``), the
    configuration and traffic, the run's shape and the device's peaks."""

    def __init__(self, red, config, traffic, run, peak, chips):
        self.red = red
        self.config = config
        self.traffic = traffic
        self.peak = peak
        self.chips = chips
        self.traced_epochs = run.traced_epochs
        self.samples_per_epoch = run.samples_per_epoch
        self.worker_steps_per_chip = run.worker_steps_per_chip

    note = staticmethod(note)

    def module_seconds(self, name: str):
        """Device seconds of XLA module ``name`` in the traced window,
        averaged over the cell's chips; ``None`` where it never ran."""
        if self.red is None:
            return None
        devs = [self.red["devices"].get(i) for i in range(self.chips)]
        if any(d is None or name not in d["modules"] for d in devs):
            return None
        return sum(d["modules"][name][0] for d in devs) / len(devs) / 1e9


def chips_or_exit(chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform == "cpu" or len(devs) < chips:
        note(f"needs {chips} accelerator chip(s); JAX found "
             f"{len(devs)} {devs[0].platform} device(s)")
        raise SystemExit(2)
    return devs


def use_cache():
    """The program's persistent compilation cache, inside the checkout (or
    where ``JAX_COMPILATION_CACHE_DIR`` says), keeping every program."""
    import jax
    from repro.runtime.compile_cache import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def compare(config, run) -> dict:
    """Every number ``check`` compares, for a job's first epoch."""
    from bench import check

    f, cap = run.follow, run.captured
    values = check.readings(
        check.program_state(cap, f, config["layer_dims"]),
        check.reference_state(config, f), f["init"])
    ref_logits = check.reference_logits(config, cap["params"], cap["topology"],
                                        f["x_test"])
    values.update(check.eval_readings(cap["eval_logits"], cap["test_acc"],
                                      ref_logits, f["y_test"]))
    return values


def run_cell(workload, seed, seconds, trace, t_start, *, require_chip=True,
             spec=None, bench=BENCH):
    """Run one cell; returns the result dict (the printed last line)."""
    import jax

    from bench import check, jobs, trace as tracemod, work

    spec = spec if spec is not None else load_spec(bench.parent)
    c = resolve(spec, workload, bench)
    cell, config, traffic = c["cell"], c["config"], c["traffic"]
    if require_chip:
        chips_or_exit(cell["chips"])
        use_cache()
    dev = jax.devices()[0]
    peak = work.peaks(dev.device_kind) if require_chip else None
    compiles = jobs.CompileCounter()
    trace_dir = None
    if trace:
        trace_dir = OUT / "trace" / workload
        shutil.rmtree(trace_dir, ignore_errors=True)
        trace_dir.mkdir(parents=True)
    run = jobs.run_job(config, dict(traffic, chips=cell["chips"]), seed, seconds,
                       t_start=t_start, compiles=compiles, trace_dir=trace_dir)
    note(f"window: {run.epochs} epochs in {run.window_s:.3f} s after "
         f"{run.setup_s:.3f} s of set-up; backend compiles inside the "
         f"window: {run.compiles_in_window}; epoch seconds "
         f"{[round(float(t), 4) for t in run.epoch_seconds]}; device peak "
         f"after making the data {run.data_peak_bytes}, after the window "
         f"{run.memory_peak_bytes}")
    values = compare(config, run)
    correct, compared = check.judge(values, c["limits"])
    losses_ok = run.epochs > 0 and all(math.isfinite(x) for x in run.losses)
    correct = correct and losses_ok
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": run.memory_peak_bytes}
    metrics, breakdown = {}, None
    if not trace:
        samples = run.epochs * run.samples_per_epoch
        found = {
            "train_samples_per_s": samples / run.window_s,
            "peak_hbm_bytes": float(run.memory_peak_bytes),
            "setup_s": run.setup_s,
        }
        for m in c["end_to_end"]:
            metrics[m["name"]] = {"value": found[m["name"]], "unit": m["unit"]}
    else:
        tr = tracemod.load(trace_dir)
        marks = tracemod.markers(tr, jobs.MARKER)
        red = tracemod.reduce(tr, marks[0], marks[-1])
        del tr
        ctx = MetricContext(red, config, traffic, run, peak, cell["chips"])
        for m in c["per_layer"]:
            value = load_reader(c["readers"][m["name"]])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        busy = [red["devices"][i]["busy_ns"] for i in range(cell["chips"])]
        device["busy_s"] = sum(busy) / len(busy) / 1e9
        device["window_s"] = red["window_ns"] / 1e9
        breakdown = tracemod.breakdown(red)
        shutil.rmtree(trace_dir, ignore_errors=True)
    result = {"correct": bool(correct), "attempted": run.epochs,
              "failed": sum(not math.isfinite(x) for x in run.losses),
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    if not losses_ok:
        note(f"window losses not all finite or no epoch run: {run.losses}")
    for name, row in compared.items():
        note(f"compared {name}: {row['value']!r} (limit {row['limit']!r})")
    result["compared"] = compared
    return result
