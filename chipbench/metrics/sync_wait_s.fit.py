"""Seconds per fit that the host waited on the device at its explicit syncs.

The sum of the fit's ``timings["to_host.<tag>"]`` spans: the host waiting
for a program to finish and copying its outputs.  The syncs of
``select_all`` are not in a fit's timings; the count of every sync stays
``host_syncs.fit``.
"""

import harness

_u = harness.load_module("metrics", "_fit_units")

PREFIX = "to_host."


def read(ctx):
    units = _u.units(ctx)
    waits = [[v for k, v in u["timings"].items() if k.startswith(PREFIX)] for u in units]
    if not units or not all(waits):
        return None
    return _u.mean(sum(w) for w in waits)
