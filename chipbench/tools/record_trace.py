#!/usr/bin/env python3
"""Record the small trace that tests/test_trace_reduce.py pins, on a TPU.

    python3 chipbench/tools/record_trace.py

Fits n=2048 blobs (d=10, kmax=16, float32 products) once to compile, then profiles a second
fit inside the harness's window span and keeps only the ``.xplane.pb``, gzipped,
as ``tests/data/fit_n2048.xplane.pb.gz``.  Prints the reduction it pins.
"""

import gzip
import json
import pathlib
import shutil
import sys

HERE = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(HERE), str(HERE.parent / "src")]

import numpy as np  # noqa: E402

import harness  # noqa: E402
import trace_reduce  # noqa: E402


def main():
    harness.set_libtpu_flags()
    harness.require_devices(1)
    harness.enable_compile_cache()
    harness.set_precision({"dtype": "float32"})
    from repro.api import FittedModel

    rng = np.random.default_rng(0)
    centers = rng.uniform(-10, 10, (10, 10))
    x = (centers[np.arange(2048) % 10] + rng.normal(0, 1, (2048, 10))).astype(np.float32)
    FittedModel.fit(x, 16).select_all()
    tmp = HERE / "out" / "record_trace"
    shutil.rmtree(tmp, ignore_errors=True)
    cell = harness.Cell("record", {}, {}, 1, [], [])
    ctx = harness.Context(cell, 0, 0.0, True, tmp)
    with ctx.traced():
        with ctx.span("fit"):
            model = FittedModel.fit(x, 16)
        with ctx.span("select_all"):
            model.select_all()
    dst = HERE / "tests" / "data" / "fit_n2048.xplane.pb.gz"
    dst.write_bytes(gzip.compress(trace_reduce.find_xplane(tmp).read_bytes()))
    s = trace_reduce.summary(trace_reduce.load(tmp))
    print(json.dumps({k: s[k] for k in ("window_s", "busy_s", "breakdown")}))
    print(f"{dst.stat().st_size} bytes")


if __name__ == "__main__":
    main()
