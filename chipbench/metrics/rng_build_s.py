"""``MultiMSTResult.timings["rng_build"]`` per fit: candidates and cascade."""

import harness

_u = harness.load_module("metrics", "_fit_units")


def read(ctx):
    return _u.mean(u["timings"]["rng_build"] for u in _u.units(ctx))
