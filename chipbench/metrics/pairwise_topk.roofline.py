"""Share of its roofline that the all-pairs kNN reaches, in %.

Work: every ordered pair of the n points, 2d operations for the product
and 3 to form the squared distance; bytes: the points once in, and the
(kmax - 1) nearest distances and ids out.  Operations bound it.

Device time: a program named for the kernel or its jnp twin
(``_knn_jnp_blocked``); on the Pallas path the kernel is an eager
``pallas_call``, which JAX runs as a program named ``jit_wrapped``.  In
this cell no other eager ``pallas_call`` runs (``lune_filter`` only serves
``variant="rng"``).
"""

import harness

_u = harness.load_module("metrics", "_fit_units")
_r = harness.load_module("metrics", "_roofline")


def read(ctx):
    if _u.traced_unit(ctx) is None:
        return None
    n, d, k = ctx.config["n"], ctx.config["d"], ctx.config["kmax"] - 1
    flops = n * n * (2 * d + 3)
    nbytes = n * d * 4 + n * k * 8
    return _r.share(ctx, flops, nbytes, "pairwise_topk", "_knn_jnp_blocked", "jit_wrapped")
