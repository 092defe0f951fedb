#!/usr/bin/env python3
"""Run one benchmark cell and print its result as the last line.

    python3 bench/run.py --workload cifar10.seq-train --seed 7 --seconds 30 --trace 0

``--trace 0`` prints the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics read from a profiler trace of the window's first epochs.
The numbers compared to decide ``correct`` are printed last on standard
error and under ``compared`` at the end of the result line. Without the
accelerator chips the cell asks for, it exits non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    from bench import harness

    result = harness.run_cell(args.workload, args.seed, args.seconds,
                              bool(args.trace), T_START)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
