#!/usr/bin/env python3
"""Readings that the limits of ``limits/<cell>.json`` are set from, for many
seeds in one process (one compile):

* ``program``: the program's first epoch against the float32 reference,
  the numbers a benchmark run compares;
* ``control``: the reference computed in bfloat16, put in the program's
  place: its first epoch, and its forward on the program's end state in
  place of the program's evaluation;
* ``half_batch``: the reference with half of every batch left out, the
  mean taken over the rest;
* ``no_exchange`` (local-SGD jobs): the reference with the average between
  workers left out.

A state left unchanged reads 1 by construction (no change where the
reference changes) and needs no run. The faults change the training only,
so they give no evaluation numbers. Each line also holds, under
``leaves``, every leaf's ``change`` and ``velocity`` reading with its
reference norm over the median leaf's.

    python3 bench/calibrate.py --workload cifar10.seq-train --seeds 1,2,3 \
        --out chiprun_out/calib.jsonl

Each seed's readings go to ``--out`` as one JSON line; the last line of
standard output gives, per number, the largest program reading and the
smallest reading of each other kind.
"""
import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def calibrate(workload, seeds, out, *, require_chip=True, spec=None, bench=None):
    import jax.numpy as jnp

    from bench import check, harness, jobs

    bench = bench or harness.BENCH
    spec = spec if spec is not None else harness.load_spec(bench.parent)
    c = harness.resolve(spec, workload, bench)
    cell, config, traffic = c["cell"], c["config"], c["traffic"]
    if require_chip:
        harness.chips_or_exit(cell["chips"])
        harness.use_cache()
    compiles = jobs.CompileCounter()
    traffic = dict(traffic, chips=cell["chips"], warmup_epochs=1)
    kinds = ["program", "control", "half_batch"]
    if traffic["job"] == "local_sgd":
        kinds.append("no_exchange")
    dims = config["layer_dims"]
    rows = []
    for seed in seeds:
        t0 = time.perf_counter()
        run = jobs.run_job(config, traffic, seed, 0.0, t_start=t0,
                           compiles=compiles, stop_at_window=True)
        f, init, cap = run.follow, run.follow["init"], run.captured
        del run
        refst = check.reference_state(config, f)
        x_te, y_te = f["x_test"], f["y_test"]
        ref_logits = check.reference_logits(config, cap["params"], cap["topology"], x_te)
        states = {"program": check.program_state(cap, f, dims)}
        row = {"seed": seed, "program": dict(
            check.readings(states["program"], refst, init),
            **check.eval_readings(cap["eval_logits"], cap["test_acc"],
                                  ref_logits, y_te))}
        states["control"] = check.reference_state(config, f, dtype=jnp.bfloat16)
        ctrl_logits = check.reference_logits(config, cap["params"], cap["topology"],
                                             x_te, dtype=jnp.bfloat16)
        ctrl_acc = float((ctrl_logits.argmax(-1) == y_te).mean())
        row["control"] = dict(
            check.readings(states["control"], refst, init),
            **check.eval_readings(ctrl_logits, ctrl_acc, ref_logits, y_te))
        states["half_batch"] = check.reference_state(config, f, half=True)
        if "no_exchange" in kinds:
            states["no_exchange"] = check.reference_state(config, f, exchange=False)
        for kind in kinds[2:]:
            row[kind] = check.readings(states[kind], refst, init)
        row["leaves"] = {}
        for kind, state in states.items():
            include, change, vel = check.leaves(state, refst, init)
            row["leaves"][kind] = {"change": check.leaf_gaps(*change, include),
                                   "velocity": check.leaf_gaps(*vel, include)}
        row["seconds"] = time.perf_counter() - t0
        rows.append(row)
        if out:
            with open(out, "a") as fh:
                fh.write(json.dumps(dict(row, workload=workload)) + "\n")
        harness.note(json.dumps({k: v for k, v in row.items() if k != "leaves"}))
    summary = {}
    for name in check.NAMES:
        summary[name] = {"program_max": max(r["program"][name] for r in rows)}
        for kind in kinds[1:]:
            got = [r[kind][name] for r in rows if name in r[kind]]
            if got:
                summary[name][f"{kind}_min"] = min(got)
    return summary


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    seeds = [int(s) for s in args.seeds.split(",")]
    print(json.dumps(calibrate(args.workload, seeds, args.out)), flush=True)


if __name__ == "__main__":
    main()
