"""Host seconds of ``select_all()`` per fit (api/model extraction)."""

import harness

_u = harness.load_module("metrics", "_fit_units")


def read(ctx):
    return _u.mean(u["extract_s"] for u in _u.units(ctx))
