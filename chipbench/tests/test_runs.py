"""Both traffic modules and every metric reader, end to end on the CPU."""

import json
import os
import shutil
import subprocess
import sys

import pytest
from conftest import HERE, TRACE, run_tiny

import harness
import trace_reduce

CELLS = ("blobs-d10.fit", "topics-d5.serve")


@pytest.fixture(scope="module", params=CELLS)
def cell_run(request):
    return request.param, *run_tiny(request.param)


def test_run_is_correct_and_reports_the_cells_metrics(cell_run):
    name, out, ctx = cell_run
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 1
    want = {m["name"] for m in harness.resolve_cell(name).end_to_end}
    assert set(out["metrics"]) == want
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert list(out)[-1] == "checks"
    assert set(out["checks"]) == set(ctx.cell.spec["limits"])
    assert ctx.counters["window"]["compiles"] == ctx.counters["setup"]["compiles"]


def test_every_per_layer_reader_reads_its_cell(cell_run):
    name, _, ctx = cell_run
    ctx.peaks = json.loads((HERE / "peaks.json").read_text())["devices"]["TPU v5 lite"]
    ctx.trace_summary = trace_reduce.summary(trace_reduce.load(TRACE))
    units = ctx.record.get("units")
    if units:
        units[0]["traced"] = True
    for m in harness.resolve_cell(name).per_layer:
        value = harness.load_module("metrics", m["name"]).read(ctx)
        assert value is not None, m["name"]
        assert value >= 0, m["name"]
        if m["unit"] == "%":
            assert value <= 100, m["name"]


def test_readers_find_nothing_without_a_trace(cell_run):
    name, _, ctx = cell_run
    ctx.trace_summary = None
    for m in harness.resolve_cell(name).per_layer:
        if m["source"] == "device_trace":
            assert harness.load_module("metrics", m["name"]).read(ctx) is None


def test_no_tpu_means_no_result(capsys):
    rc = harness.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                       "--trace", "0"])
    assert rc != 0
    assert capsys.readouterr().out == ""


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(HERE.parent / "BENCHMARK.json", tmp_path)
    shutil.copytree(HERE, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("out", "__pycache__", "data"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run([sys.executable, "chipbench/run.py", "--workload", CELLS[0],
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_schedule_is_one_set_in_another_order():
    spec = json.loads((HERE / "workloads" / "topics-d5.serve.json").read_text())
    traffic = harness.load_module("traffic", "open_loop_predict")
    a = traffic.schedule(spec["params"], 40.0, 1)
    b = traffic.schedule(spec["params"], 40.0, 2**31 + 99)
    for x, y in zip(a[1:], b[1:]):
        assert sorted(x) == sorted(y)
    assert abs(a[0][-1] - b[0][-1]) < 40.0 * 0.2


def test_a_trace_with_dropped_buffers_gives_no_result(monkeypatch):
    real = trace_reduce.summary

    def dropped(pd):
        try:
            s = real(pd)
        except ValueError:  # the CPU's trace has no device plane
            s = real(trace_reduce.load(TRACE))
        return dict(s, dropped=3)

    monkeypatch.setattr(trace_reduce, "summary", dropped)
    with pytest.raises(harness.BenchError, match="dropped 3 trace buffers"):
        run_tiny(CELLS[0], trace=1)
