"""engine.trace: host spans, counters and compile time, and the fit's spans.

CPU only.  A span times host work and annotates the profiler's trace; a
recording collects the spans, counters and compiles of its own thread.
"""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import engine
from repro.api import FittedModel
from repro.core import multi
from repro.engine import trace

# every span a fused-path fit records inside fit_msts
FIT_SPANS = (
    "knn", "rng_build", "rng.wspd", "rng.emit", "rng.cascade", "rng.graph_host",
    "mst_pad", "mst_range", "to_host.knn", "to_host.candidate_count",
    "to_host.stage1_count", "to_host.graph", "to_host.mst",
)
RNG_CHILDREN = (
    "rng.wspd", "rng.emit", "to_host.candidate_count", "rng.cascade",
    "to_host.graph", "rng.graph_host",
)


def _blobs(n=2048, d=10, seed=0):
    rng = np.random.default_rng(seed)
    centers = rng.uniform(-10, 10, (10, d))
    return (centers[np.arange(n) % 10] + rng.normal(0, 1, (n, d))).astype(np.float32)


@pytest.fixture(scope="module")
def fitted():
    """A warm fused-path fit (jnp backend) under the transfer ledger, and
    its length on the host clock."""
    x = _blobs()
    multi.fit_msts(x, 16, backend="jnp")  # compiles
    with engine.transfer_ledger() as led:
        t0 = time.perf_counter()
        msts = multi.fit_msts(x, 16, backend="jnp")
        wall = time.perf_counter() - t0
    return msts, engine.io.tags(led), wall


# -- the recorder --------------------------------------------------------


def test_spans_nest_with_parent_ids_and_inclusive_seconds():
    with trace.recording() as rec:
        with trace.span("rng_build"):
            with trace.span("rng.wspd"):
                pass
            with trace.span("rng.emit"):
                with trace.span("to_host.graph"):
                    pass
        with trace.span("rng.emit"):
            pass
    by_id = {sid: (parent, name, t0, t1) for sid, parent, name, t0, t1 in rec.spans}
    names = {name: [] for _, name, _, _ in by_id.values()}
    for sid, (parent, name, _, _) in by_id.items():
        names[name].append((sid, parent))
    (build, root), = names["rng_build"]
    assert root is None
    assert [p for _, p in names["rng.wspd"]] == [build]
    (inner_emit, inner_parent), (_, outer_parent) = sorted(names["rng.emit"])
    assert inner_parent == build and outer_parent is None
    (_, sync_parent), = names["to_host.graph"]
    assert sync_parent == inner_emit
    for sid, (parent, _, t0, t1) in by_id.items():
        assert t0 <= t1
        if parent is not None:
            assert by_id[parent][2] <= t0 and t1 <= by_id[parent][3]
    seconds = rec.seconds()
    emit = [t1 - t0 for _, name, t0, t1 in by_id.values() if name == "rng.emit"]
    assert seconds["rng.emit"] == pytest.approx(sum(emit))
    assert seconds["rng_build"] >= seconds["rng.wspd"] + max(emit)


def test_counters_add_up_and_need_a_recording():
    trace.count("emit.tier_dispatches")  # no recording: nothing to add to
    with trace.recording() as rec:
        trace.count("emit.tier_dispatches")
        trace.count("emit.tier_dispatches", 2)
        trace.count("emit.upload_bytes", 4096)
    c = rec.counters()
    assert c["emit.tier_dispatches"] == 3 and c["emit.upload_bytes"] == 4096
    assert "emit.rowpath_dispatches" not in c


@pytest.mark.parametrize("call, name", [
    (trace.span, "rng.bogus"),
    (trace.span, "tohost.graph"),
    (trace.count, "emit.bogus"),
])
def test_undeclared_names_are_refused(call, name):
    with pytest.raises(ValueError, match="not declared"):
        if call is trace.span:
            with call(name):
                pass
        else:
            call(name)


def test_sync_spans_take_any_tag():
    with trace.recording() as rec:
        with trace.span("to_host.anything"):
            pass
    assert list(rec.seconds()) == ["to_host.anything"]


def test_recordings_are_per_thread():
    start, release = threading.Event(), threading.Event()
    seen = {}

    def other():
        with trace.recording() as rec:
            with trace.span("rng.wspd"):
                trace.count("emit.rowpath_dispatches")
            start.set()
            release.wait(10)
        seen["other"] = rec

    t = threading.Thread(target=other)
    t.start()
    assert start.wait(10)
    with trace.recording() as mine:
        with trace.span("rng.emit"):
            trace.count("emit.tier_dispatches")
    release.set()
    t.join(10)
    assert not t.is_alive()
    assert list(mine.seconds()) == ["rng.emit"]
    assert "emit.rowpath_dispatches" not in mine.counters()
    assert list(seen["other"].seconds()) == ["rng.wspd"]
    assert "emit.tier_dispatches" not in seen["other"].counters()


def test_nested_recordings_both_see_the_inner_work():
    with trace.recording() as outer:
        with trace.span("knn"):
            pass
        with trace.recording() as inner:
            with trace.span("mst_pad"):
                trace.count("emit.upload_bytes", 8)
    assert set(outer.seconds()) == {"knn", "mst_pad"}
    assert set(inner.seconds()) == {"mst_pad"}
    assert outer.counters()["emit.upload_bytes"] == 8


def test_a_fresh_compile_lands_in_its_own_recording_only():
    start, release = threading.Event(), threading.Event()
    seen = {}

    def idle():
        with trace.recording() as rec:
            start.set()
            release.wait(30)
        seen["idle"] = rec.counters()

    t = threading.Thread(target=idle)
    t.start()
    assert start.wait(10)
    with trace.recording() as before:
        pass
    with trace.recording() as rec:
        with trace.span("knn"):
            jax.jit(lambda v: v * 3.0 + 1.0)(jnp.arange(11.0)).block_until_ready()
    release.set()
    t.join(30)
    assert not t.is_alive()
    c = rec.counters()
    assert c["compiles"] >= 1
    assert 0 < c["compile"] <= rec.seconds()["knn"]
    assert seen["idle"]["compiles"] == 0 and seen["idle"]["compile"] == 0
    assert before.counters()["compiles"] == 0


def test_compile_is_the_union_of_nested_events():
    trace_event, lower_event, compile_event = trace.COMPILE_EVENTS
    with trace.recording() as rec:
        jax.monitoring.record_event_time_span(compile_event, 100.0, 101.0)
        jax.monitoring.record_event_time_span(lower_event, 100.2, 100.5)
        jax.monitoring.record_event_time_span(trace_event, 102.0, 102.5)
        jax.monitoring.record_event_time_span("/jax/some/other_event", 0.0, 50.0)
    c = rec.counters()
    assert c["compile"] == pytest.approx(1.5) and c["compiles"] == 1


# -- the fit's spans -----------------------------------------------------


@pytest.mark.parametrize("name", FIT_SPANS + ("compile", "compiles"))
def test_fit_timings_hold_every_span(fitted, name):
    msts, _, _ = fitted
    assert msts.timings[name] >= 0


def test_stage_spans_tile_the_fit(fitted):
    msts, _, wall = fitted
    t = msts.timings
    assert t["rng_build"] >= sum(t[k] for k in RNG_CHILDREN)
    assert t["rng.cascade"] >= t["to_host.stage1_count"]
    assert t["mst_range"] >= t["to_host.mst"] and t["knn"] >= t["to_host.knn"]
    stages = sum(t[k] for k in multi.FIT_STAGES)
    assert stages <= wall
    assert wall - stages <= 0.02 + 0.1 * wall


def test_fit_counts_its_emission_and_keeps_its_syncs(fitted):
    msts, tags, _ = fitted
    stats = msts.graph.stats
    assert stats["path"] == "fused"
    assert stats["emit_dispatches"] > 0 and stats["emit_upload_bytes"] > 0
    assert tags == ["knn", "candidate_count", "stage1_count", "graph", "mst"]


def test_saved_model_with_span_timings_loads(profiled, tmp_path):
    model = profiled[2]
    loaded = FittedModel.load(model.save(str(tmp_path / "m.npz")))
    assert set(loaded.msts.timings) >= set(FIT_SPANS)
    assert loaded.msts.timings == pytest.approx(model.msts.timings)
    assert loaded.msts.graph.stats["emit_dispatches"] == model.msts.graph.stats["emit_dispatches"]


def test_extraction_timings_come_from_its_spans(fitted):
    msts, _, _ = fitted
    with trace.recording() as rec:
        _, timings = multi.extract_hierarchies(msts)
    seconds = rec.seconds()
    assert timings["hierarchy_linkage"] == seconds["extract.linkage"] > 0
    assert timings["hierarchy_condense"] == seconds["extract.condense"] > 0
    assert timings["hierarchy"] == pytest.approx(
        timings["hierarchy_linkage"] + timings["hierarchy_condense"])
    assert seconds["extract.linkage"] >= seconds["to_host.linkage"]


# -- the spans in the profiler's trace -----------------------------------


@pytest.fixture(scope="module")
def profiled(tmp_path_factory, fitted):
    """A fit under jax.profiler on the CPU, with its recording."""
    x = _blobs()
    out = tmp_path_factory.mktemp("profile")
    with jax.profiler.trace(str(out)):
        with trace.recording() as rec:
            model = FittedModel.fit(x, 16, backend="jnp")
    path = sorted(out.rglob("*.xplane.pb"))[-1]
    pd = jax.profiler.ProfileData.from_file(str(path))
    names = set(trace.SPANS)
    events = {}
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in names or e.name.startswith(trace.SYNC_PREFIX):
                    events.setdefault(e.name, []).append(
                        (e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9))
    for v in events.values():
        v.sort()
    return rec, events, model


def test_every_span_is_a_host_event_nested_in_its_parent(profiled):
    rec, events, _ = profiled
    spans = sorted(rec.spans, key=lambda s: s[3])
    assert {name for _, _, name, _, _ in spans} >= set(FIT_SPANS) | {"fit.provenance"}
    seen: dict[str, int] = {}
    matched = {}
    for sid, parent, name, t0, t1 in spans:
        k = seen.get(name, 0)
        seen[name] = k + 1
        assert len(events.get(name, ())) > k, name
        start, end = events[name][k]
        assert abs((end - start) - (t1 - t0)) <= 1e-3 + 0.05 * (t1 - t0), name
        matched[sid] = (start, end)
    for sid, parent, name, _, _ in spans:
        if parent is not None:
            (ps, pe), (s, e) = matched[parent], matched[sid]
            assert ps <= s and e <= pe, name
