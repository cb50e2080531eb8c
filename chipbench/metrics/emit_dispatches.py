"""``graph["emit_dispatches"]`` per fit: launches of the emission programs."""

import harness

_k = harness.load_module("metrics", "_unit_keys")


def read(ctx):
    return _k.mean(ctx, "graph", "emit_dispatches")
