"""CPU tests of the benchmark: traffic, readers and checks at a tiny size.

Every run here stubs the harness's look for a TPU; nothing here is a device
measurement.
"""

import copy
import json
import pathlib
import sys
import time

import pytest

HERE = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(HERE), str(HERE.parent / "src")]

import harness  # noqa: E402

DATA = HERE / "tests" / "data"
TRACE = DATA / "fit_n2048.xplane.pb.gz"
SEED = 2**31 + 12345
TINY = {"blobs-d10.fit": {"n": 1200}, "topics-d5.serve": {"n": 1500}}
TINY_SERVE = {"rate_rows_per_s": 150, "rows_max": 8, "max_batch": 8, "check_requests": 400}


# The serve cell is parked: the program cannot yet run it on the chip (see
# PERF.md, Open questions), so BENCHMARK.json leaves it out.  Its entries are
# kept here, and the tests read BENCHMARK.json with them added, so that the
# traffic module and its readers stay tested on the CPU until the cell returns.
PARKED = json.loads((DATA / "parked_serve_cell.json").read_text())
_load_json = harness.load_json


def _with_parked(path):
    data = _load_json(path)
    if path.name == "BENCHMARK.json":
        data = {k: v + PARKED.get(k, []) if isinstance(v, list) else v
                for k, v in data.items()}
    return data


harness.load_json = _with_parked
_resolve = harness.resolve_cell


def tiny_cell(name):
    cell = _resolve(name)
    cell.config = dict(cell.config, **TINY[name])
    if "rate_rows_per_s" in cell.spec.get("params", {}):
        cell.spec = copy.deepcopy(cell.spec)
        cell.spec["params"].update(TINY_SERVE)
    return cell


def fake_device(chips):
    return {"platform": "cpu", "kind": "TPU v5 lite", "count": chips}


def run_tiny(name, *, seed=SEED, seconds=2.0, trace=0):
    """One harness run of a cell at its tiny size, and its Context."""
    seen = {}
    orig_ctx = harness.Context

    class Recording(orig_ctx):
        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            seen["ctx"] = self

    mp = pytest.MonkeyPatch()
    mp.setattr(harness, "resolve_cell", tiny_cell)
    mp.setattr(harness, "Context", Recording)
    try:
        args = harness.parse_args(["--workload", name, "--seed", str(seed),
                                   "--seconds", str(seconds), "--trace", str(trace)])
        out = harness.run(args, time.monotonic(), devices=fake_device)
    finally:
        mp.undo()
    return out, seen["ctx"]
