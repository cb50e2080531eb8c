"""The readers of the program's own spans and counters, on unit records and
on recorded traces."""

import types

import pytest
from conftest import DATA, TRACE

import harness
import trace_reduce

# one fit and extraction of the same 2048 blobs, recorded on one TPU v5 lite
# by chipbench/tools/record_trace.py once the program had its spans
SPANS_TRACE = DATA / "fit_n2048_spans.xplane.pb.gz"

UNIT = {
    "timings": {"knn": 0.5, "rng_build": 9.0, "rng.wspd": 4.0, "rng.emit": 3.0,
                "to_host.knn": 0.25, "to_host.graph": 0.5, "to_host.mst": 0.75,
                "compile": 1.25, "compiles": 2.0},
    "graph": {"emit_dispatches": 1200, "path": "fused"},
}
PLAIN = {"timings": {"knn": 0.5, "rng_build": 9.0, "mst_range": 0.5},
         "graph": {"path": "fused"}}

READERS = {  # name: value on two units of UNIT, the second twice as slow
    "wspd_s": 6.0,
    "emit_host_s": 4.5,
    "emit_dispatches": 1800,
    "sync_wait_s.fit": 2.25,
    "compile_s.fit": 1.875,
}


def _ctx(units=(), summary=None, trace_dir=None):
    return types.SimpleNamespace(record={"units": list(units)}, trace_summary=summary,
                                 trace_dir=trace_dir, config={}, peaks=None)


def _double(unit):
    return {k: {kk: v * 2 if not isinstance(v, str) else v for kk, v in d.items()}
            for k, d in unit.items()}


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_averages_the_untraced_units(name):
    traced = dict(_double(_double(UNIT)), traced=True)
    ctx = _ctx([UNIT, _double(UNIT), traced])
    assert harness.load_module("metrics", name).read(ctx) == pytest.approx(READERS[name])


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_finds_nothing_where_the_program_records_nothing(name):
    assert harness.load_module("metrics", name).read(_ctx([PLAIN, PLAIN])) is None
    assert harness.load_module("metrics", name).read(_ctx([UNIT, PLAIN])) is None
    assert harness.load_module("metrics", name).read(_ctx()) is None


def _idle(trace, **kw):
    summary = trace_reduce.summary(trace_reduce.load(trace))
    ctx = _ctx(summary=summary, trace_dir=trace, **kw)
    return harness.load_module("metrics", "idle_unattributed.fit").read(ctx), ctx


def test_a_trace_without_program_spans_is_all_unattributed():
    value, ctx = _idle(TRACE)
    assert value == pytest.approx(100.0)
    reader = harness.load_module("metrics", "idle_unattributed.fit")
    gaps = reader.idle_gaps(ctx.trace_profile, lambda name: False)
    s = ctx.trace_summary
    assert sum(g for g, _ in gaps) == pytest.approx(s["window_s"] - s["busy_s"], rel=1e-9)
    assert len(gaps) > len(s["breakdown"]["idle_gaps"])


def test_the_programs_spans_cover_its_idle_time():
    value, ctx = _idle(SPANS_TRACE)
    assert 0 <= value < 10
    from repro.engine import trace

    host = {e.name for p in trace_reduce.planes(ctx.trace_profile)[1]
            for line in p.lines for e in line.events}
    fit = {"knn", "rng_build", "rng.wspd", "rng.emit", "rng.cascade", "rng.graph_host",
           "mst_pad", "mst_range", "extract.linkage", "extract.condense", "fit.provenance"}
    assert fit <= host & set(trace.SPANS)
    assert {"to_host.knn", "to_host.graph", "to_host.mst", "to_host.linkage"} <= host


def test_every_gap_counts_and_each_span_covers_its_own():
    reader = harness.load_module("metrics", "idle_unattributed.fit")
    pd = trace_reduce.load(TRACE)
    # JAX's runtime events stand in for program spans: the kNN re-trace
    # covers about 0.51 of the 0.75 s idle in this fit
    gaps = reader.idle_gaps(pd, lambda name: name.startswith("PjitFunction(wrapped"))
    share = sum(g for g, ok in gaps if ok) / sum(g for g, _ in gaps)
    assert 0.5 < share < 0.9
    everything = reader.idle_gaps(pd, lambda name: True)
    assert all(ok for _, ok in everything)


def test_without_the_trace_file_the_summary_gives_an_upper_bound(tmp_path):
    reader = harness.load_module("metrics", "idle_unattributed.fit")
    summary = trace_reduce.summary(trace_reduce.load(TRACE))
    ctx = _ctx(summary=summary, trace_dir=tmp_path)
    assert reader.read(ctx) == pytest.approx(100.0)
    labelled = dict(summary, breakdown={"idle_gaps": [["fit > rng.wspd", 0.25]]})
    idle = summary["window_s"] - summary["busy_s"]
    assert reader.read(_ctx(summary=labelled, trace_dir=tmp_path)) == pytest.approx(
        100.0 * (idle - 0.25) / idle)


def test_no_trace_means_nothing_to_read():
    reader = harness.load_module("metrics", "idle_unattributed.fit")
    assert reader.read(_ctx(trace_dir=TRACE)) is None
