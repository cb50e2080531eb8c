"""Device-to-host syncs per fit and extraction, from ``engine.io``'s ledger."""

import harness

_u = harness.load_module("metrics", "_fit_units")


def read(ctx):
    return _u.mean(u["host_syncs"] for u in _u.units(ctx))
