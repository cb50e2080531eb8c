"""Full-range fits, back to back, on one dataset drawn from the seed.

One unit of work is ``FittedModel.fit(X, kmax)`` and then ``select_all()``
on that fresh model: kNN, candidate graph, all R MSTs, all R condensed
trees with their labels.  Set-up draws the data and runs one unit, which
compiles (or loads) every program.  The window runs units until the first
one that ends after ``--seconds``; ``fit_s`` is the window's length over
the units it completed.  With ``--trace 1`` the first unit of the window is
profiled.

The check runs the reference over the same data once the window has closed
and compares every unit's answers: the sorted MST weights of every mpts
row (kNN, core distances, candidate graph, cascade and MST range all show
there) and the labels of every mpts row (extraction).
"""

from __future__ import annotations

import time

import numpy as np

import reference

DATA = 0  # seed purpose: the fitted points


def _unit(ctx, X):
    from repro import engine
    from repro.api import FittedModel

    cfg = ctx.config
    t0 = time.monotonic()
    with engine.io.transfer_ledger(guard=False) as ledger:
        with ctx.span("fit"):
            model = FittedModel.fit(X, cfg["kmax"], kmin=cfg["kmin"])
        t1 = time.monotonic()
        with ctx.span("select_all"):
            views = model.select_all()
    t2 = time.monotonic()
    out = {
        "seconds": t2 - t0,
        "extract_s": t2 - t1,
        "timings": dict(model.msts.timings),
        "host_syncs": len(ledger),
        "graph": dict(model.msts.graph.stats),
        "weights": np.sort(model.msts.mst_w, axis=1),
        "views": views,
        "stage1_k": model.plan.cascade_stage1_k,
    }
    return out


def setup(ctx):
    cfg = ctx.config
    ctx.X = ctx.dataset(cfg["n"], DATA)
    ctx.mpts_values = list(range(cfg["kmin"], cfg["kmax"] + 1))
    _unit(ctx, ctx.X)  # compiles, or loads from the persistent cache


def window(ctx):
    units = []
    t0 = time.monotonic()
    while True:
        if ctx.trace and not units:
            with ctx.traced():
                u = _unit(ctx, ctx.X)
            u["traced"] = True
        else:
            u = _unit(ctx, ctx.X)
            u["traced"] = False
        units.append(u)
        if time.monotonic() - t0 >= ctx.seconds:
            break
    elapsed = time.monotonic() - t0
    ctx.record.update(
        units=units,
        attempted=len(units),
        failed=0,
        end_to_end={"fit_s": elapsed / len(units)},
    )
    print(f"fit_loop: {len(units)} units in {elapsed:.4f} s; per unit "
          + ", ".join(f"{u['seconds']:.4f}" for u in units), flush=True)


def _readings(units, weights, trees):
    """The numbers compared: MST weights, and labels with memberships."""
    gap = max(reference.weight_gap(u["weights"], weights) for u in units)
    answers = 0.0
    for u in units:
        for r, tree in enumerate(trees):
            lab, prob = u["answers"][r]
            mapping = reference.label_map(lab, tree.labels)
            answers = max(answers, reference.answer_gap(
                lab, prob, tree.labels, tree.probabilities, mapping))
    return {"mst_weight_gap": gap, "extract_gap": answers}


def check(ctx):
    """The window's answers against the float32 reference."""
    units = ctx.record["units"]
    for u in units:
        u["answers"] = [(v.labels, v.probabilities) for v in u.pop("views")]
    weights, trees, _ = reference.fit(ctx.X, ctx.config["kmax"], ctx.mpts_values)
    return _readings(units, weights, trees)


def control(ctx):
    """The reference in bfloat16, put in the program's place."""
    weights, trees, _ = reference.fit(ctx.X, ctx.config["kmax"], ctx.mpts_values)
    w_lo, t_lo, _ = reference.fit(ctx.X, ctx.config["kmax"], ctx.mpts_values, "bfloat16")
    unit = {"weights": w_lo, "answers": [(t.labels, t.probabilities) for t in t_lo]}
    return _readings([unit], weights, trees)
