"""Least time for a kernel's work on the chip, from the peaks table.

The work is counted from the stage's real inputs (n, d, k, the real edge
counts), never from the padded buffers or the kernel's grid, so a change
that cuts padding or swaps a kernel for its twin is read against the same
work.  The least time is the larger of operations over the bf16 peak and
bytes over HBM bandwidth; the share is that over the measured device time.
"""

import trace_reduce


def share(ctx, flops: float, nbytes: float, *names: str):
    s = ctx.trace_summary
    if not s or not ctx.peaks:
        return None
    t = trace_reduce.device_seconds(s, *names)
    if t <= 0:
        return None
    least = max(flops / ctx.peaks["flops_bf16"], nbytes / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / t
