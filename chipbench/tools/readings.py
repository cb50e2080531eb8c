#!/usr/bin/env python3
"""Readings that the cells' limits are set from, on a TPU, in one process.

    python3 chipbench/tools/readings.py --workload <cell> --seeds 1 2 ... \\
        --control-seeds 1 2 3 [--seconds 5]

For each of ``--seeds``: set-up, a window of ``--seconds`` and the cell's
own check, as a run makes them (the lower readings).  For each of
``--control-seeds``: the same set-up and window, then the cell's control,
the reference in bfloat16 put in the program's place (the upper readings).
Prints one JSON line per seed and a last line with the largest program
reading and the smallest control reading of each number.
"""

import argparse
import json
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(HERE), str(HERE.parent / "src")]

import harness  # noqa: E402


def one(cell, traffic, seed, seconds, kind):
    ctx = harness.Context(cell, seed, seconds, False, HERE / "out" / "readings")
    t0 = time.monotonic()
    traffic.setup(ctx)
    traffic.window(ctx)
    got = traffic.check(ctx) if kind == "program" else traffic.control(ctx)
    row = {"kind": kind, "seed": seed, **got, "seconds": time.monotonic() - t0}
    print(json.dumps(row), flush=True)
    return got


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, nargs="*", default=[])
    p.add_argument("--control-seeds", type=int, nargs="*", default=[])
    p.add_argument("--seconds", type=float, default=0.0)
    a = p.parse_args()
    cell = harness.resolve_cell(a.workload)
    harness.set_libtpu_flags()
    harness.require_devices(cell.chips)
    harness.enable_compile_cache()
    harness.set_precision(cell.config)
    traffic = harness.load_module("traffic", cell.spec["traffic"])
    lower = [one(cell, traffic, s, a.seconds, "program") for s in a.seeds]
    upper = [one(cell, traffic, s, a.seconds, "control") for s in a.control_seeds]
    names = sorted(set().union(*lower, *upper))
    print(json.dumps({
        "lower": {k: max(r[k] for r in lower) for k in names if lower},
        "upper": {k: min(r[k] for r in upper) for k in names if upper},
    }), flush=True)


if __name__ == "__main__":
    main()
