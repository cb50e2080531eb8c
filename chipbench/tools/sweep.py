#!/usr/bin/env python3
"""Find the knee of an open-loop predict cell once, on a TPU.

    python3 chipbench/tools/sweep.py --workload topics-d5.serve \\
        --rates 400 600 800 --seconds 10 --seed 1

One set-up (fit and warm-up), then one window per offered rate, lowest
first, each with the cell's own mix and batching.  Prints one JSON line per
rate: offered and answered rows per second, latency percentiles, the
backlog at the close and the rows per device pass.  The knee is the highest
rate answered in full within its window with a p95 that has not yet turned
up steeply.
"""

import argparse
import json
import pathlib
import sys

import numpy as np

HERE = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(HERE), str(HERE.parent / "src")]

import harness  # noqa: E402


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", type=float, nargs="+", required=True)
    p.add_argument("--seconds", type=float, default=10.0)
    p.add_argument("--seed", type=int, default=1)
    a = p.parse_args()
    cell = harness.resolve_cell(a.workload)
    harness.set_libtpu_flags()
    harness.require_devices(cell.chips)
    harness.enable_compile_cache()
    harness.set_precision(cell.config)
    traffic = harness.load_module("traffic", cell.spec["traffic"])
    ctx = harness.Context(cell, a.seed, a.seconds, False, HERE / "out" / "sweep")
    traffic.setup(ctx)
    for rate in sorted(a.rates):
        traffic.plan_requests(ctx, rate)
        ctx.record = {}
        traffic.window(ctx)
        rec = ctx.record
        lat = rec["latency_ms"]
        before, after = rec["serve_before"], rec["serve_after"]
        passes = max(1, after["n_batches"] - before["n_batches"])
        print(json.dumps({
            "offered_rows_per_s": float(ctx.rows.sum()) / a.seconds,
            "answered_rows_per_s": rec["end_to_end"]["predict_rows_per_s"],
            "requests": rec["attempted"], "failed": rec["failed"],
            "p50_ms": float(np.percentile(lat, 50)),
            "p95_ms": rec["end_to_end"]["predict_p95_ms"],
            "p99_ms": float(np.percentile(lat, 99)),
            "rows_per_pass": (after["n_queries"] - before["n_queries"]) / passes,
        }), flush=True)
    ctx.engine.close()


if __name__ == "__main__":
    main()
