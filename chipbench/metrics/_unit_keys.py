"""Shared by the readers of the program's own spans and counters.

A unit carries ``timings`` (``MultiMSTResult.timings``: the fit's spans and
compile time) and ``graph`` (the rng build's ``stats``).  A program that
records no such span or counter leaves the key out, and then the reader
has nothing to read.
"""

import harness

_u = harness.load_module("metrics", "_fit_units")


def mean(ctx, section: str, key: str):
    """``unit[section][key]`` averaged over the untraced units, or None."""
    units = _u.units(ctx)
    if not units or any(key not in u[section] for u in units):
        return None
    return _u.mean(u[section][key] for u in units)
