"""Share of the device's idle time in one traced fit that no span of the
program accounts for, in %.

Every idle gap of the first device within the window counts, not only the
longest.  A gap is attributed when a span of the program covers its middle:
a name in ``repro.engine.trace.SPANS``, or ``to_host.<tag>``.  The
benchmark's own spans and JAX's runtime events do not count.  The share is
the seconds of unattributed gaps over all idle seconds.  A program that
declares no spans gives nothing to read.

The trace is loaded once from ``ctx.trace_dir`` and kept on ``ctx``.  Where
the run kept only the trace's summary, its labelled longest gaps stand in
and the rest of the idle time counts as unattributed: an upper bound.
"""

import trace_reduce


def _program_spans():
    try:
        from repro.engine import trace
    except ImportError:
        return None
    names = frozenset(trace.SPANS)
    return lambda name: name in names or name.startswith(trace.SYNC_PREFIX)


def _profile(ctx):
    if not hasattr(ctx, "trace_profile"):
        try:
            ctx.trace_profile = trace_reduce.load(ctx.trace_dir)
        except FileNotFoundError:
            ctx.trace_profile = None
    return ctx.trace_profile


def idle_gaps(pd, is_span):
    """(seconds, attributed) for every idle gap of the first device plane
    within the window, or None when the trace has no device plane."""
    dev, host = trace_reduce.planes(pd)
    if not dev:
        return None
    events = [ev for p in host for line in p.lines for ev in trace_reduce._events(line)]
    window = [ev for ev in events if ev[0] == trace_reduce.WINDOW]
    if window:
        lo, hi = window[0][1], window[0][2]
    else:
        every = events + [ev for p in dev for line in p.lines
                          for ev in trace_reduce._events(line)]
        lo, hi = min(e[1] for e in every), max(e[2] for e in every)
    programs = [(s, e) for line in dev[0].lines if line.name in trace_reduce.MODULE_LINES
                for _, s, e in trace_reduce._events(line)]
    busy = trace_reduce._clip(trace_reduce._union(programs), lo, hi)
    edges = [lo] + [t for iv in busy for t in iv] + [hi]
    spans = [(s, e) for name, s, e in events if is_span(name)]
    out = []
    for s, e in zip(edges[0::2], edges[1::2]):
        if e > s:
            mid = (s + e) / 2
            out.append(((e - s) * 1e-9, any(a <= mid <= b for a, b in spans)))
    return out


def _summary_gaps(summary, is_span):
    """The summary's labelled gaps, and the rest of the idle time unlabelled."""
    listed = [(secs, any(is_span(part) for part in label.split(" > ")))
              for label, secs in summary["breakdown"]["idle_gaps"]]
    rest = summary["window_s"] - summary["busy_s"] - sum(s for s, _ in listed)
    return listed + [(max(rest, 0.0), False)]


def read(ctx):
    is_span = _program_spans()
    if not ctx.trace_summary or is_span is None:
        return None
    pd = _profile(ctx)
    gaps = idle_gaps(pd, is_span) if pd is not None else None
    if gaps is None:
        gaps = _summary_gaps(ctx.trace_summary, is_span)
    idle = sum(s for s, _ in gaps)
    if idle <= 0:
        return None
    return 100.0 * sum(s for s, ok in gaps if not ok) / idle
