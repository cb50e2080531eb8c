"""The one reduction from a profiler trace to busy time, idle gaps and
per-program device time.

``load(path)`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote under a
directory (or a file given directly) with nothing but JAX.  ``summary``
reduces it:

* the window is the host span named ``WINDOW`` (the harness opens it around
  the traced work), else the extent of every event in the trace;
* a device plane is one that carries a program line (``XLA Modules``):
  one per chip; the trace's other ``/device:`` planes (a ``CUSTOM``
  Megascale plane) hold no device work and would dilute the average;
* busy time is, per device plane, the union of its program events within
  the window, averaged over the device planes; a program runs from its
  first op to its last, with the loop control between them;
* per-op and per-program device time sum the durations of the events on
  the op and module lines, keyed by name (a trailing ``(<n>)`` id is
  dropped, so the runs of one program add up); ops nest (a loop and its
  body), so only programs add up to busy time, and the breakdown lists
  programs; a trace taken without op events has programs only;
* each idle gap on the first device is labelled by the innermost host
  event that covers its middle, under the benchmark span around it;
* ``dropped`` counts the trace buffers the chips dropped (the device
  planes' ``dropped_traces``): a chip that runs out of trace buffers
  drops the rest of the span, so a trace with drops is incomplete.
"""

from __future__ import annotations

import gzip
import pathlib
import re

WINDOW = "chipbench window"
BENCH_SPANS = ("fit", "select_all", "predict pass")
OP_LINES = ("XLA Ops",)
MODULE_LINES = ("XLA Modules",)
_ID = re.compile(r"\(\d+\)$")


def find_xplane(path) -> pathlib.Path:
    path = pathlib.Path(path)
    if path.is_file():
        return path
    found = sorted(path.rglob("*.xplane.pb")) + sorted(path.rglob("*.xplane.pb.gz"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def load(path):
    """A trace from a directory, an ``.xplane.pb`` or a gzipped one."""
    import jax

    path = find_xplane(path)
    if path.suffix == ".gz":
        return jax.profiler.ProfileData.from_serialized_xspace(
            gzip.decompress(path.read_bytes()))
    return jax.profiler.ProfileData.from_file(str(path))


def _events(line):
    for e in line.events:
        yield e.name, float(e.start_ns), float(e.start_ns + e.duration_ns)


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def _key(name: str) -> str:
    return _ID.sub("", name).strip()


def planes(pd):
    """(device planes, host planes): a device plane has a program line."""
    dev, host = [], []
    for p in pd.planes:
        if p.name.startswith("/device:") and any(
                line.name in MODULE_LINES for line in p.lines):
            dev.append(p)
        elif p.name.startswith("/host:"):
            host.append(p)
    return dev, host


def summary(pd, top: int = 10) -> dict:
    dev_planes, host_planes = planes(pd)
    if not dev_planes:
        raise ValueError("the trace has no device plane")
    host_events = [ev for p in host_planes for line in p.lines for ev in _events(line)]
    spans = [ev for ev in host_events if ev[0] == WINDOW]
    if spans:
        lo, hi = spans[0][1], spans[0][2]
    else:
        every = host_events + [ev for p in dev_planes for line in p.lines
                               for ev in _events(line)]
        lo, hi = min(e[1] for e in every), max(e[2] for e in every)

    ops: dict[str, float] = {}
    modules: dict[str, float] = {}
    busy = []
    first_busy = None
    for p in dev_planes:
        intervals = []
        for line in p.lines:
            is_op, is_mod = line.name in OP_LINES, line.name in MODULE_LINES
            if not (is_op or is_mod):
                continue
            for name, s, e in _events(line):
                if e <= lo or s >= hi:
                    continue
                dur = (min(e, hi) - max(s, lo)) * 1e-9
                table = ops if is_op else modules
                table[_key(name)] = table.get(_key(name), 0.0) + dur
                if is_mod:
                    intervals.append((s, e))
        merged = _clip(_union(intervals), lo, hi)
        busy.append(sum(e - s for s, e in merged))
        if first_busy is None:
            first_busy = merged

    edges = [lo] + [x for iv in first_busy for x in iv] + [hi]
    gaps = sorted(((e - s, s, e) for s, e in zip(edges[0::2], edges[1::2]) if e > s),
                  reverse=True)[:top]
    return {
        "dropped": sum(int(v) for p in dev_planes for k, v in p.stats
                       if k == "dropped_traces"),
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy) / len(busy) * 1e-9,
        "ops": ops,
        "modules": modules,
        "breakdown": {
            "device_ops": sorted(([k, v] for k, v in modules.items()),
                                 key=lambda kv: -kv[1])[:top],
            "idle_gaps": [[_label(host_events, (s + e) / 2), g * 1e-9]
                          for g, s, e in gaps],
        },
    }


def _label(host_events, t) -> str:
    """``<benchmark span> > <innermost host event>`` covering time t."""
    covering = [ev for ev in host_events if ev[1] <= t <= ev[2] and ev[0] != WINDOW]
    bench = [ev for ev in covering if ev[0] in BENCH_SPANS]
    inner = min(covering, key=lambda ev: ev[2] - ev[1], default=None)
    outer = min(bench, key=lambda ev: ev[2] - ev[1], default=None)
    parts = [ev[0] for ev in (outer, inner) if ev is not None]
    if len(parts) == 2 and parts[0] == parts[1]:
        parts = parts[:1]
    return " > ".join(parts) if parts else "no host span"


def device_seconds(summary_: dict, *names: str) -> float:
    """Device time of the ops or programs whose names contain any of names.

    Ops are searched first; programs only where no op matches, so a kernel
    that is both an op and its own program is not counted twice.
    """
    for table in (summary_["ops"], summary_["modules"]):
        t = sum(v for k, v in table.items() if any(n in k for n in names))
        if t > 0:
            return t
    return 0.0


def describe(pd, per_line: int = 5) -> str:
    """Planes, lines and their first event names: for reading a trace by hand."""
    out = []
    for p in pd.planes:
        out.append(f"plane {p.name!r}")
        for line in p.lines:
            names = [e.name for _, e in zip(range(per_line), line.events)]
            out.append(f"  line {line.name!r}: {names}")
    return "\n".join(out)


if __name__ == "__main__":
    import json
    import sys

    data = load(sys.argv[1])
    print(describe(data))
    s = summary(data)
    print(json.dumps({k: s[k] for k in ("window_s", "busy_s", "breakdown")}, indent=1))
    print(json.dumps(sorted(s["modules"].items(), key=lambda kv: -kv[1])[:25], indent=1))
