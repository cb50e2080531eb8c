"""trace_reduce on a small trace recorded on one TPU v5 lite: one fit and
extraction of 2048 blobs, d=10, kmax=16, made by chipbench/tools/record_trace.py."""

import pytest
from conftest import TRACE

import trace_reduce

WINDOW_S = 0.9815194070000001
BUSY_S = 0.23431157600000002
PROGRAMS = {
    "jit__edge_cascade_pallas": 0.024707698000000004,
    "jit__mst_stage_local": 0.053590936000000006,
    "jit_wrapped": 0.003062083,
}


@pytest.fixture(scope="module")
def summary():
    return trace_reduce.summary(trace_reduce.load(TRACE))


def test_window_is_the_harness_span(summary):
    assert summary["window_s"] == pytest.approx(WINDOW_S, rel=1e-9)


def test_busy_is_the_union_of_device_ops(summary):
    assert summary["busy_s"] == pytest.approx(BUSY_S, rel=1e-9)
    assert 0 < summary["busy_s"] < summary["window_s"]


def test_only_planes_with_programs_are_devices():
    # The trace also holds an empty "/device:CUSTOM:Megascale Trace" plane;
    # counting it as a chip would halve the busy time.
    pd = trace_reduce.load(TRACE)
    assert any(p.name.startswith("/device:CUSTOM") for p in pd.planes)
    assert [p.name for p in trace_reduce.planes(pd)[0]] == ["/device:TPU:0"]


def test_program_device_time(summary):
    for name, seconds in PROGRAMS.items():
        assert summary["modules"][name] == pytest.approx(seconds, rel=1e-9)
    assert trace_reduce.device_seconds(summary, "edge_cascade") == pytest.approx(
        PROGRAMS["jit__edge_cascade_pallas"], rel=1e-9)


def test_breakdown_lists_programs_and_labelled_gaps(summary):
    ops, gaps = summary["breakdown"]["device_ops"], summary["breakdown"]["idle_gaps"]
    assert 0 < len(ops) <= 10 and 0 < len(gaps) <= 10
    assert [s for _, s in ops] == sorted((s for _, s in ops), reverse=True)
    assert all(label.split(" > ")[0] in trace_reduce.BENCH_SPANS for label, _ in gaps)
    idle = summary["window_s"] - summary["busy_s"]
    assert sum(s for _, s in gaps) <= idle * (1 + 1e-9)


class _Plane:
    def __init__(self, plane, extra):
        self.name, self.lines = plane.name, list(plane.lines)
        self.stats = list(plane.stats) + extra


class _Profile:
    """The recorded trace, as if its chip had dropped trace buffers."""

    def __init__(self, pd, dropped):
        self.planes = [_Plane(p, [("dropped_traces", dropped)]
                              if p.name.startswith("/device:TPU") else [])
                       for p in pd.planes]


def test_dropped_trace_buffers_are_counted(summary):
    assert summary["dropped"] == 0
    assert trace_reduce.summary(_Profile(trace_reduce.load(TRACE), 188))["dropped"] == 188


class _ProgramsOnly(_Profile):
    """The recorded trace without its op lines, as the harness's runs record it."""

    def __init__(self, pd):
        super().__init__(pd, 0)
        for p in self.planes:
            p.lines = [line for line in p.lines if line.name not in trace_reduce.OP_LINES]


def test_a_trace_of_programs_alone_reads_the_same(summary):
    s = trace_reduce.summary(_ProgramsOnly(trace_reduce.load(TRACE)))
    assert s["ops"] == {}
    assert s["busy_s"] == pytest.approx(summary["busy_s"], rel=1e-12)
    assert trace_reduce.device_seconds(s, "edge_cascade") == pytest.approx(
        PROGRAMS["jit__edge_cascade_pallas"], rel=1e-9)
    assert trace_reduce.device_seconds(s, "pairwise_topk", "jit_wrapped") == pytest.approx(
        PROGRAMS["jit_wrapped"], rel=1e-9)


def test_libtpu_flags_are_added_once(monkeypatch):
    import harness

    monkeypatch.setenv("LIBTPU_INIT_ARGS", "--xla_tpu_use_enhanced_launch_barrier=true")
    harness.set_libtpu_flags()
    harness.set_libtpu_flags()
    args = harness.os.environ["LIBTPU_INIT_ARGS"].split()
    assert args[0] == "--xla_tpu_use_enhanced_launch_barrier=true"
    assert all(args.count(f) == 1 for f in harness.LIBTPU_FLAGS)
