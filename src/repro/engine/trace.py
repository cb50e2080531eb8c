"""Host spans and counters: what the host is doing while the device waits.

A *span* names one stage of host work.  ``SPANS`` lists every name; each
explicit device->host sync adds ``to_host.<tag>`` (``engine.io.to_host``).
Every span enters a ``jax.profiler.TraceAnnotation``, so while a profile is
being taken it lands on the host plane of the same trace as the device's
programs, on one clock with them; and it times itself with
``time.perf_counter``.  A *recording* collects, for the thread that opened
it, every span and counter, and the time JAX spent tracing, lowering and
compiling::

    with trace.recording() as rec:
        with trace.span("knn"):
            ...
    rec.seconds()    # {"knn": ..., "to_host.knn": ...}: inclusive, summed
    rec.counters()   # {"compile": s, "compiles": n, "emit.tier_dispatches": ...}

Three rules keep the spans honest and cheap:

* a span never synchronises the device: it measures host time, and device
  time stays with the device trace (a sync is ``to_host``'s own span);
* spans are per stage and per sync, never per dispatch, so a fit records a
  few dozen whatever its size; per-dispatch events are ``count``-ed;
* spans live in host code only, never inside a jitted or Pallas body
  (there they would run once, at trace time).
"""

from __future__ import annotations

import contextlib
import itertools
import threading
import time

import jax

SPANS = (
    "knn", "rng_build", "rng.wspd", "rng.dualtree", "rng.emit", "rng.cascade",
    "rng.graph_host", "mst_pad", "mst_range", "extract.linkage",
    "extract.condense", "fit.provenance", "baseline.mst", "baseline.extract",
)
SYNC_PREFIX = "to_host."
COUNTERS = ("emit.tier_dispatches", "emit.rowpath_dispatches", "emit.upload_bytes")

# JAX's compile events, reported in the compiling thread with their start
# and end.  They nest (a persistent-cache read lies inside the backend
# compile, a nested jit's trace inside its caller's), so a recording keeps
# their intervals and ``compile`` is the length of their union.
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
COMPILE_EVENTS = (_TRACE_EVENT, _LOWER_EVENT, _COMPILE_EVENT)

_NAMES = frozenset(SPANS)
_ids = itertools.count(1)
_listen_lock = threading.Lock()
_listening = False


class Recording:
    """Spans, counters and compile time of one thread over a stretch of work."""

    def __init__(self):
        self.spans: list[tuple[int, int | None, str, float, float]] = []
        self._counts: dict[str, float] = {}
        self._compile: list[tuple[float, float]] = []
        self._compiles = 0

    def seconds(self) -> dict[str, float]:
        """Per span name, its inclusive duration summed over occurrences."""
        out: dict[str, float] = {}
        for _, _, name, t0, t1 in self.spans:
            out[name] = out.get(name, 0.0) + (t1 - t0)
        return out

    def counters(self) -> dict[str, float]:
        """The counts, with ``compile`` (seconds) and ``compiles``: the
        programs handed to the compiler or read from the persistent cache."""
        covered, end = 0.0, float("-inf")
        for s, e in sorted(self._compile):
            covered += max(0.0, e - max(s, end))
            end = max(end, e)
        return {**self._counts, "compile": covered, "compiles": self._compiles}


class _Local(threading.local):
    def __init__(self):
        self.recordings: list[Recording] = []
        self.stack: list[int] = []


_local = _Local()


@contextlib.contextmanager
def span(name: str):
    """Time the enclosed host work as ``name`` and annotate the profile."""
    if name not in _NAMES and not name.startswith(SYNC_PREFIX):
        raise ValueError(f"span {name!r} is not declared in engine.trace.SPANS")
    sid = next(_ids)
    stack = _local.stack
    parent = stack[-1] if stack else None
    stack.append(sid)
    t0 = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(name):
            yield
    finally:
        t1 = time.perf_counter()
        stack.pop()
        for rec in _local.recordings:
            rec.spans.append((sid, parent, name, t0, t1))


def count(name: str, n: float = 1) -> None:
    """Add ``n`` to counter ``name`` in this thread's open recordings."""
    if name not in COUNTERS:
        raise ValueError(f"counter {name!r} is not declared in engine.trace.COUNTERS")
    for rec in _local.recordings:
        rec._counts[name] = rec._counts.get(name, 0) + n


@contextlib.contextmanager
def recording():
    """Record this thread's spans, counters and compiles until exit.

    Recordings nest: each open one sees everything its thread does.
    """
    _listen()
    rec = Recording()
    _local.recordings.append(rec)
    try:
        yield rec
    finally:
        _local.recordings.remove(rec)


def _on_compile(event: str, start: float, end: float, **_) -> None:
    if event not in COMPILE_EVENTS:
        return
    for rec in _local.recordings:
        rec._compile.append((start, end))
        if event == _COMPILE_EVENT:
            rec._compiles += 1


def _listen() -> None:
    global _listening
    with _listen_lock:
        if not _listening:
            jax.monitoring.register_event_time_span_listener(_on_compile)
            _listening = True
