"""``timings["rng.emit"]`` per fit: the host's gathers, uploads and dispatch
of every SBCN emission program and the sort that dedups their keys.  On the
fused path the span holds no sync."""

import harness

_k = harness.load_module("metrics", "_unit_keys")


def read(ctx):
    return _k.mean(ctx, "timings", "rng.emit")
