"""Share of its roofline that the fused edge cascade reaches, in %.

Per edge checked against k neighbours of each endpoint (dimension d): the
edge's own squared distance (3d operations) and each of the 2k candidate
occupants' squared distances to both endpoints (12kd); bytes are the two
endpoint ids and core distances, both endpoint rows, per candidate its id,
stored distance and row, and the four outputs (26 + 8d + 2k(8 + 4d)).
Bytes bound it.

Stage 1 checks every real candidate slot (``m_candidate_slots``) against
``stage1_k`` neighbours.  Stage 2 checks the open survivors of stage 1
against ``kmax - 1``; the program does not report their count, so the kept
open edges (``m_edges - m_certified``), which are a subset of them, stand
in: the work is a lower bound and so is the share.
"""

import harness

_u = harness.load_module("metrics", "_fit_units")
_r = harness.load_module("metrics", "_roofline")


def work(d: int, k: int, edges: int):
    return edges * (3 * d + 12 * k * d), edges * (26 + 8 * d + 2 * k * (8 + 4 * d))


def read(ctx):
    u = _u.traced_unit(ctx)
    if u is None:
        return None
    g, d, kmax = u["graph"], ctx.config["d"], ctx.config["kmax"]
    if g.get("path") != "fused":
        return None
    f1, b1 = work(d, u["stage1_k"], g["m_candidate_slots"])
    f2, b2 = work(d, kmax - 1, g["m_edges"] - g["m_certified"])
    return _r.share(ctx, f1 + f2, b1 + b2, "edge_cascade", "stage1_packed")
