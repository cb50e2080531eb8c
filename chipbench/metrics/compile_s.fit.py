"""``timings["compile"]`` per fit: seconds JAX spent tracing, lowering and
compiling (or reading the persistent cache) inside a warm fit."""

import harness

_k = harness.load_module("metrics", "_unit_keys")


def read(ctx):
    return _k.mean(ctx, "timings", "compile")
