"""Idle share of the device over the traced slice of the predict window, in %."""


def read(ctx):
    s = ctx.trace_summary
    if not s or s["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - s["busy_s"] / s["window_s"])
