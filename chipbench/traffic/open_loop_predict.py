"""Open-loop ``approximate_predict`` traffic against one fitted model.

Set-up fits the configuration's data, starts a ``ClusterServeEngine`` with
the cell's batching knobs and warms every shape the traffic can send: each
query-row count from 1 to ``max_batch`` and each number of distinct mpts
levels in one pass.

The schedule is the same set of requests for every seed, in another order:
``schedule_seed`` draws the request sizes (rows, heavy-tailed over
``1..rows_max`` with P(r) ~ r^-rows_alpha) until they add up to
``rate_rows_per_s`` times the window, their mpts levels from ``mpts_mix``,
and exponential gaps rescaled to span the window.  The run's seed shuffles
the three lists and draws the query points from the configuration's own
distribution.

One thread sends each request when it is due.  A request's latency runs
from when it was due until its future resolves, so a stalled sender or a
backlog shows.  ``predict_rows_per_s`` counts the rows answered inside the
window over the window's length.  With ``--trace 1`` the first
``trace_seconds`` of the window are profiled.

The check runs the reference once the window has closed: it fits the same
points itself at the levels the traffic used and predicts a sample of the
served requests (drawn from the seed, the largest among them), and compares
labels (through the fitted labelling) and membership probabilities.
"""

from __future__ import annotations

import threading
import time

import numpy as np

import reference
from harness import log, seed_seq

DATA, QUERIES, ORDER, SAMPLE = 0, 1, 2, 3
LATE_WAIT_S = 60.0


def schedule(params: dict, seconds: float, seed: int):
    """(due_s, rows, mpts) per request, in the order they are sent."""
    rng = np.random.default_rng(params["schedule_seed"])
    r = np.arange(1, params["rows_max"] + 1)
    p = r ** -float(params["rows_alpha"])
    p /= p.sum()
    target = params["rate_rows_per_s"] * seconds
    rows = []
    while sum(rows) < target:
        rows.append(int(rng.choice(r, p=p)))
    levels = np.asarray([int(k) for k in params["mpts_mix"]])
    share = np.asarray(list(params["mpts_mix"].values()), np.float64)
    mpts = rng.choice(levels, size=len(rows), p=share / share.sum())
    gaps = rng.exponential(size=len(rows))
    gaps *= seconds / gaps.sum()
    order = np.random.default_rng(seed_seq(seed, ORDER))
    rows = order.permutation(np.asarray(rows))
    mpts = order.permutation(mpts)
    gaps = order.permutation(gaps)
    due = np.cumsum(gaps) - gaps[0]
    return due, rows, mpts


def plan_requests(ctx, rate_rows_per_s: float):
    """The window's requests and their query rows, at the given rate."""
    prm = dict(ctx.params, rate_rows_per_s=rate_rows_per_s)
    ctx.due, ctx.rows, ctx.mpts = schedule(prm, ctx.seconds, ctx.seed)
    ctx.Q = ctx.dataset(int(ctx.rows.sum()), QUERIES)


def setup(ctx):
    from repro.api import FittedModel
    from repro.serve import ClusterServeEngine

    cfg, prm = ctx.config, ctx.params
    ctx.X = ctx.dataset(cfg["n"], DATA)
    t0 = time.monotonic()
    model = FittedModel.fit(ctx.X, cfg["kmax"], kmin=cfg["kmin"])
    log(f"open_loop_predict: fit {time.monotonic() - t0:.4f} s")
    if ctx.trace:  # a span around each device pass the engine makes
        passes = model.predict_range

        def predict_range(*a, **k):
            with ctx.span("predict pass"):
                return passes(*a, **k)

        model.predict_range = predict_range
    ctx.engine = ClusterServeEngine(
        model, max_batch=prm["max_batch"], max_delay_ms=prm["max_delay_ms"],
        hierarchy_cache_size=prm["hierarchy_cache_size"])
    plan_requests(ctx, prm["rate_rows_per_s"])
    levels = sorted(int(k) for k in prm["mpts_mix"])
    widest = max(prm["max_batch"], prm["rows_max"])
    warm = ctx.dataset(widest, QUERIES + 100)
    for q in range(1, widest + 1):
        ctx.engine.predict(warm[:q], mpts=levels[0])
    for k in range(1, len(levels) + 1):
        model.predict_range(warm[:prm["max_batch"]], mpts_values=levels[:k])
    for m in levels:
        ctx.engine.predict(warm[:1], mpts=m)
    log(f"open_loop_predict: set-up done {time.monotonic() - t0:.4f} s after the fit began")


def window(ctx):
    eng = ctx.engine
    n_req = len(ctx.due)
    starts = np.concatenate([[0], np.cumsum(ctx.rows)])
    done_at = np.full(n_req, np.nan)
    sent_at = np.full(n_req, np.nan)
    results: list = [None] * n_req
    errors = []
    all_done = threading.Event()
    pending = [n_req]
    lock = threading.Lock()

    def finish(i, fut):
        done_at[i] = time.monotonic()
        try:
            results[i] = fut.result()
        except Exception as e:  # noqa: BLE001 - a failed request counts as failed
            errors.append((i, repr(e)))
        with lock:
            pending[0] -= 1
            if pending[0] == 0:
                all_done.set()

    before = eng.stats()
    t0 = time.monotonic()
    trace_end = t0 + ctx.params["trace_seconds"]
    tracing = ctx.traced()
    tracing.__enter__()
    traced = ctx.trace
    for i in range(n_req):
        due = t0 + ctx.due[i]
        if traced and due >= trace_end:
            tracing.__exit__(None, None, None)
            traced = False
        while (now := time.monotonic()) < due:
            time.sleep(min(due - now, 0.002))
        sent_at[i] = time.monotonic()
        fut = eng.submit_predict(ctx.Q[starts[i]:starts[i + 1]], mpts=int(ctx.mpts[i]))
        fut.add_done_callback(lambda f, i=i: finish(i, f))
    if traced:
        tracing.__exit__(None, None, None)
    t_close = t0 + ctx.seconds
    while (now := time.monotonic()) < t_close:
        time.sleep(min(t_close - now, 0.05))
    after = eng.stats()
    all_done.wait(timeout=max(0.0, t_close + LATE_WAIT_S - time.monotonic()))

    due_abs = t0 + ctx.due
    lat = done_at - due_abs
    failed = int(np.isnan(lat).sum()) + len(errors)
    lat_ms = np.where(np.isnan(lat), np.inf, lat * 1e3)
    in_window = done_at <= t_close
    answered_rows = int(ctx.rows[in_window].sum())
    late = (sent_at - due_abs) * 1e3
    ctx.results = results
    ctx.record.update(
        attempted=n_req,
        failed=failed,
        end_to_end={
            "predict_p95_ms": float(np.percentile(lat_ms, 95, method="higher")),
            "predict_rows_per_s": answered_rows / ctx.seconds,
        },
        latency_ms=lat_ms,
        serve_before=before,
        serve_after=after,
    )
    print(f"open_loop_predict: {n_req} requests, {int(ctx.rows.sum())} rows offered, "
          f"{answered_rows} answered in the window; latency p50 "
          f"{np.percentile(lat_ms, 50):.3f} ms p95 {np.percentile(lat_ms, 95):.3f} ms "
          f"max {np.max(lat_ms):.3f} ms; sender late p95 {np.nanpercentile(late, 95):.3f} ms "
          f"max {np.nanmax(late):.3f} ms; errors {errors[:3]}", flush=True)


def _sample(ctx):
    """Served requests to compare, drawn from the seed, the largest among them."""
    rng = np.random.default_rng(seed_seq(ctx.seed, SAMPLE))
    n_req = len(ctx.due)
    k = min(n_req, ctx.params["check_requests"])
    sample = set(rng.choice(n_req, size=k, replace=False).tolist())
    sample.add(int(np.argmax(ctx.rows)))
    return sorted(i for i in sample if ctx.results[i] is not None)


def _compare(ctx, sample, answer, fitted):
    """predict_gap of ``answer(m, q, rows)`` against the reference.

    ``fitted[m]`` is the fitted labelling of whoever answered, which maps its
    label names onto the reference's.
    """
    cfg = ctx.config
    levels = sorted(int(k) for k in ctx.params["mpts_mix"])
    _, trees, cd2 = reference.fit(ctx.X, cfg["kmax"], levels)
    starts = np.concatenate([[0], np.cumsum(ctx.rows)])
    gap, rows = 0.0, 0
    for m, tree in zip(levels, trees):
        mine = [i for i in sample if ctx.mpts[i] == m]
        if not mine:
            continue
        q = np.concatenate([ctx.Q[starts[i]:starts[i + 1]] for i in mine])
        want_lab, want_p = reference.predict(q, ctx.X, cd2, tree, m)
        got_lab, got_p = answer(m, q, mine)
        mapping = reference.label_map(fitted[m], tree.labels)
        gap = max(gap, reference.answer_gap(got_lab, got_p, want_lab, want_p, mapping))
        rows += len(q)
    print(f"open_loop_predict check: {len(sample)} requests, {rows} rows", flush=True)
    return {"predict_gap": gap}


def check(ctx):
    """A sample of the served answers against the float32 reference."""
    levels = sorted(int(k) for k in ctx.params["mpts_mix"])
    model = ctx.engine.model
    ctx.engine.close()
    fitted = {m: model.select(m).labels for m in levels}
    del ctx.engine, model

    def served(m, q, mine):
        return (np.concatenate([ctx.results[i][0] for i in mine]),
                np.concatenate([ctx.results[i][1] for i in mine]))

    return _compare(ctx, _sample(ctx), served, fitted)


def control(ctx):
    """The reference in bfloat16, put in the program's place."""
    ctx.engine.close()
    del ctx.engine
    levels = sorted(int(k) for k in ctx.params["mpts_mix"])
    _, trees, cd2 = reference.fit(ctx.X, ctx.config["kmax"], levels, "bfloat16")
    tree_of = dict(zip(levels, trees))
    fitted = {m: t.labels for m, t in tree_of.items()}

    def lower(m, q, mine):
        return reference.predict(q, ctx.X, cd2, tree_of[m], m, "bfloat16")

    return _compare(ctx, _sample(ctx), lower, fitted)
