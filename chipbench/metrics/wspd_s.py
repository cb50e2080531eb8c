"""``timings["rng.wspd"]`` per fit: the host's fair-split tree and pair walk."""

import harness

_k = harness.load_module("metrics", "_unit_keys")


def read(ctx):
    return _k.mean(ctx, "timings", "rng.wspd")
