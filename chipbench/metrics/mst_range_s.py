"""``MultiMSTResult.timings["mst_range"]`` per fit: reweight and Boruvka."""

import harness

_u = harness.load_module("metrics", "_fit_units")


def read(ctx):
    return _u.mean(u["timings"]["mst_range"] for u in _u.units(ctx))
