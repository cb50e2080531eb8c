"""End-to-end driver: "over one hundred hierarchies for the cost of two".

Staged pipeline (each stage reusable on its own; ``repro.api.MultiHDBSCAN``
is the front door that composes them lazily):

  ``fit_msts``          — one (kmax-1)-NN pass, one RNG^kmax, reweight for the
                          whole mpts range, batched Borůvka: all R MSTs as
                          (R, n-1) edge arrays.  Device-heavy, done once.
  ``linkage_range``     — stage 1 of extraction: all R single-linkage
                          dendrograms in ONE vmapped device program
                          (core.linkage), no per-edge Python loop.
  ``extract_hierarchies`` / ``extract_one_from_linkage``
                        — stage 2: vectorized condense/stability/labels
                          (core.hierarchy fast path) per requested mpts, so
                          hierarchies materialize on demand.

``multi_hdbscan``  — the paper's method end-to-end (eager extraction of the
whole range), kept as the one-call entry point for scripts and tests.

``hdbscan_baseline`` — the paper's *optimized* comparison baseline: the same
single kNN pass (core distances shared across the range), then an O(n^2)
complete-graph MST per mpts (dense Prim, nothing materialized).

Both return per-mpts hierarchies/labels through the same batched extraction,
so benchmark ratios isolate exactly the graph/MST work the paper optimizes.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .. import engine
from ..engine import trace
from . import hierarchy, linkage
from . import boruvka
from . import mrd as mrd_mod
from .rng import RngGraph, build_rng_graph


@dataclasses.dataclass
class HierarchyResult:
    mpts: int
    labels: np.ndarray
    n_clusters: int
    condensed: hierarchy.CondensedTree
    stability: dict[int, float]  # every condensed cluster, selected or not
    mst_ea: np.ndarray
    mst_eb: np.ndarray
    mst_w: np.ndarray  # real (non-squared) mrd weights
    selected: list[int] = dataclasses.field(default_factory=list)  # chosen cluster ids
    point_lambda: np.ndarray | None = None  # (n,) departure lambda (0 for noise)


def _validate_min_cluster_size(min_cluster_size: int | None) -> None:
    if min_cluster_size is not None and min_cluster_size < 2:
        raise ValueError(
            f"min_cluster_size must be >= 2 (or None for the per-mpts "
            f"default max(2, mpts)); got {min_cluster_size}"
        )


@dataclasses.dataclass
class MultiMSTResult:
    """Everything shared across the mpts range, before any extraction."""

    n: int
    kmax: int
    mpts_values: list[int]
    graph: RngGraph | None
    knn_d2: np.ndarray
    knn_idx: np.ndarray
    cd2: np.ndarray
    mst_ea: np.ndarray  # (R, n-1) int32: MST edges per mpts row
    mst_eb: np.ndarray  # (R, n-1) int32
    mst_w: np.ndarray   # (R, n-1) float32, real (non-squared) mrd weights
    timings: dict[str, float]

    def row_of(self, mpts: int) -> int:
        try:
            return self.mpts_values.index(mpts)
        except ValueError:
            raise KeyError(
                f"mpts={mpts} not in computed range {self.mpts_values}"
            ) from None


@dataclasses.dataclass
class LinkageRange:
    """Stage-1 extraction output: all R dendrograms, scipy convention."""

    left: np.ndarray    # (R, n-1) int32
    right: np.ndarray   # (R, n-1) int32
    height: np.ndarray  # (R, n-1) float32, ascending per row
    size: np.ndarray    # (R, n-1) int32


@dataclasses.dataclass
class MultiDensityResult:
    n: int
    kmax: int
    mpts_values: list[int]
    graph: RngGraph
    knn_d2: np.ndarray
    knn_idx: np.ndarray
    cd2: np.ndarray
    hierarchies: list[HierarchyResult]
    timings: dict[str, float]


# The spans that tile ``fit_msts``: their seconds add up to the whole fit.
FIT_STAGES = ("knn", "rng_build", "mst_pad", "mst_range")


@functools.partial(jax.jit, static_argnames=("n",))
def _mst_stage_local(d2_pad, cd2_dev, ea, eb, row_idx, *, n: int):
    """Single-device MST stage as ONE program: reweight + batched Borůvka +
    row compaction, no intermediate materialization between steps."""
    w_range = mrd_mod.reweight_all_mpts(d2_pad, cd2_dev, ea, eb)
    w_sel = w_range[row_idx]
    in_mst = boruvka.boruvka_mst_range(ea, eb, w_sel, n=n)
    return _compact_mst_rows(in_mst, ea, eb, w_sel, n=n)


@functools.partial(jax.jit, static_argnames=("n",))
def _compact_mst_rows(in_mst, ea, eb, w_sel, *, n: int):
    """(R, m) MST mask -> (R, n-1) ascending edge-id compaction + counts."""
    R, m = in_mst.shape
    rows = jnp.arange(R, dtype=jnp.int32)[:, None]
    dst = jnp.where(in_mst, jnp.cumsum(in_mst, axis=1, dtype=jnp.int32) - 1, n - 1)
    sel = (
        jnp.zeros((R, n - 1), jnp.int32)
        .at[rows, dst]
        .set(jnp.broadcast_to(jnp.arange(m, dtype=jnp.int32), (R, m)), mode="drop")
    )
    counts = jnp.sum(in_mst, axis=1, dtype=jnp.int32)
    mst_w = jnp.sqrt(jnp.take_along_axis(w_sel, sel, axis=1))
    return ea[sel], eb[sel], mst_w, counts


def fit_msts(
    x,
    kmax: int,
    *,
    kmin: int = 2,
    variant: str = "rng_star",
    backend: str | None = None,
    mpts_values: Sequence[int] | None = None,
    plan: "engine.Plan | str | None" = None,
) -> MultiMSTResult:
    """kNN -> RNG^kmax -> reweight-all-mpts -> batched Borůvka, no extraction.

    A thin composition over the resolved ``plan``: every stage is a device
    program placed by the plan (single device or mesh), and each stage ends
    at exactly one named ``engine.to_host`` materialization — ``knn`` (the
    host view stored on the result, which also feeds the WSPD control
    plane), ``graph`` (inside build_rng_graph), and ``mst`` (the final MST
    compaction, the MST stage's single device->host sync; the row masks are
    compacted to (R, n-1) edge ids on device first).

    ``timings`` is the fit's ``engine.trace`` recording: the seconds of each
    span (``FIT_STAGES`` tile the fit; ``rng.*`` and ``to_host.<tag>`` nest
    inside them), and ``compile`` / ``compiles`` for JAX's compiles.
    """
    plan = plan if isinstance(plan, engine.Plan) else engine.resolve_plan(plan, backend=backend)
    x_host = engine.io.ensure_host(x)
    n = x_host.shape[0]
    if kmax < 2 or kmax > n:
        raise ValueError(f"kmax must be in [2, n]; got {kmax} (n={n})")
    mpts_list = list(mpts_values) if mpts_values is not None else list(range(kmin, kmax + 1))
    if any(m < 1 or m > kmax for m in mpts_list):
        raise ValueError(f"mpts values must lie in [1, kmax]; got {mpts_list}")

    with trace.recording() as rec:
        with trace.span("knn"):
            x = jnp.asarray(x_host)
            knn_d2, knn_idx = plan.knn(x, kmax - 1, x_host=x_host)
            cd2_dev = mrd_mod.core_distances2(knn_d2)
            knn_host, knn_idx_host, cd2 = engine.to_host((knn_d2, knn_idx, cd2_dev), "knn")

        with trace.span("rng_build"):
            graph = build_rng_graph(
                x,
                knn_d2,
                knn_idx,
                variant=variant,
                plan=plan,
                x_host=x_host,
                cd_kmax_host=np.sqrt(cd2[:, -1].astype(np.float64)),
                knn_d2_host=knn_host,
                knn_idx_host=knn_idx_host,
            )

        # quantize the edge count so the Borůvka/reweight programs compile one
        # shape per scale bucket instead of one per dataset; padded edges are
        # (0, 0) with +inf weight — same component, never cross, never chosen
        with trace.span("mst_pad"):
            m_real = len(graph.edges)
            m_pad = max(4096, -(-m_real // 4096) * 4096)
            ea = jnp.zeros((m_pad,), jnp.int32).at[:m_real].set(
                jnp.asarray(graph.edges[:, 0], jnp.int32)
            )
            eb = jnp.zeros((m_pad,), jnp.int32).at[:m_real].set(
                jnp.asarray(graph.edges[:, 1], jnp.int32)
            )
            d2_pad = jnp.full((m_pad,), jnp.inf, jnp.float32).at[:m_real].set(
                jnp.asarray(graph.d2)
            )

        with trace.span("mst_range"):
            row_idx = jnp.asarray([m - 1 for m in mpts_list])
            if plan.sharded:
                w_range = mrd_mod.reweight_all_mpts(d2_pad, cd2_dev, ea, eb)
                w_sel = w_range[row_idx]
                in_mst = plan.mst_range(ea, eb, w_sel, n=n)
                mst_dev = _compact_mst_rows(in_mst, ea, eb, w_sel, n=n)
            else:
                # single device: reweight + Borůvka + row compaction fused into one
                # program (each row's mask compacts to (n-1) ascending edge ids via
                # cumsum-positioned scatters), ending at the stage's one host sync
                mst_dev = _mst_stage_local(d2_pad, cd2_dev, ea, eb, row_idx, n=n)
            mst_ea, mst_eb, mst_w, counts = engine.to_host(mst_dev, "mst")
            if not np.all(counts == n - 1):
                # Borůvka exits via progressed=False on a disconnected edge list and
                # returns < n-1 edges per row; consuming those rows downstream would
                # feed garbage into linkage.  The RNG^kmax provably contains every
                # per-mpts MST (paper Cor. 1), so disconnection here always means an
                # upstream candidate/filter bug (or a hand-fed broken edge list) —
                # fail loudly instead.
                bad = {
                    mpts_list[i]: int(counts[i])
                    for i in np.flatnonzero(counts != n - 1)
                }
                raise RuntimeError(
                    f"MST incomplete: graph variant {variant!r} with "
                    f"{m_real} edges is disconnected — got "
                    f"{{mpts: n_tree_edges}} = {bad}, need {n - 1} edges per mpts. "
                    f"The RNG^kmax must contain every MST, so this indicates an "
                    f"upstream candidate-generation or filter bug."
                )
    counters = rec.counters()
    timings = {
        **rec.seconds(),
        "compile": counters["compile"],
        "compiles": float(counters["compiles"]),
    }

    return MultiMSTResult(
        n=n,
        kmax=kmax,
        mpts_values=mpts_list,
        graph=graph,
        knn_d2=knn_host,
        knn_idx=knn_idx_host,
        cd2=cd2,
        mst_ea=mst_ea,
        mst_eb=mst_eb,
        mst_w=mst_w,
        timings=timings,
    )


@trace.span("extract.linkage")
def linkage_range(msts: MultiMSTResult) -> LinkageRange:
    """All of the range's dendrograms in one batched device program.

    Row i of the result corresponds to ``msts.mpts_values[i]``.
    """
    left, right, height, size = engine.to_host(
        linkage.single_linkage_batch(
            msts.mst_ea, msts.mst_eb, msts.mst_w, n=msts.n
        ),
        "linkage",
    )
    return LinkageRange(left=left, right=right, height=height, size=size)


# -- artifact pack/unpack ----------------------------------------------------
#
# The fitted device state is host numpy by the time it lives on a
# MultiMSTResult (every stage ends at a named engine.to_host point), so an
# artifact is a flat dict of arrays plus a small JSON-able meta dict.  The
# api.FittedModel save/load layer owns the file format; these two functions
# own WHAT constitutes the fitted state, so a field added to MultiMSTResult
# fails loudly here instead of silently vanishing from artifacts.


def pack_msts(msts: MultiMSTResult) -> tuple[dict[str, np.ndarray], dict]:
    """Split a MultiMSTResult into (arrays, meta) for serialization.

    ``arrays`` values are host numpy (``engine.io.ensure_host`` guards
    against device arrays sneaking in); ``meta`` is JSON-serializable.
    """
    arrays = {
        "knn_d2": msts.knn_d2,
        "knn_idx": msts.knn_idx,
        "cd2": msts.cd2,
        "mst_ea": msts.mst_ea,
        "mst_eb": msts.mst_eb,
        "mst_w": msts.mst_w,
        "mpts_values": np.asarray(msts.mpts_values, np.int64),
    }
    meta: dict = {
        "n": int(msts.n),
        "kmax": int(msts.kmax),
        "timings": {k: float(v) for k, v in msts.timings.items()},
        "graph": None,
    }
    if msts.graph is not None:
        arrays["graph_edges"] = msts.graph.edges
        arrays["graph_d2"] = msts.graph.d2
        arrays["graph_w2_kmax"] = msts.graph.w2_kmax
        meta["graph"] = {
            "variant": msts.graph.variant,
            "n_points": int(msts.graph.n_points),
            "stats": {
                k: (int(v) if isinstance(v, (int, np.integer)) else v)
                for k, v in msts.graph.stats.items()
            },
        }
    return (
        {k: engine.io.ensure_host(v) for k, v in arrays.items()},
        meta,
    )


def unpack_msts(arrays: dict[str, np.ndarray], meta: dict) -> MultiMSTResult:
    """Inverse of ``pack_msts``; raises KeyError on missing array fields."""
    graph = None
    if meta.get("graph") is not None:
        g = meta["graph"]
        graph = RngGraph(
            edges=arrays["graph_edges"],
            d2=arrays["graph_d2"],
            w2_kmax=arrays["graph_w2_kmax"],
            variant=g["variant"],
            n_points=int(g["n_points"]),
            stats=dict(g["stats"]),
        )
    return MultiMSTResult(
        n=int(meta["n"]),
        kmax=int(meta["kmax"]),
        mpts_values=[int(m) for m in arrays["mpts_values"]],
        graph=graph,
        knn_d2=arrays["knn_d2"],
        knn_idx=arrays["knn_idx"],
        cd2=arrays["cd2"],
        mst_ea=arrays["mst_ea"],
        mst_eb=arrays["mst_eb"],
        mst_w=arrays["mst_w"],
        timings={k: float(v) for k, v in meta.get("timings", {}).items()},
    )


@trace.span("extract.condense")
def extract_one_from_linkage(
    msts: MultiMSTResult,
    lk: LinkageRange,
    row: int,
    *,
    min_cluster_size: int | None = None,
    allow_single_cluster: bool = False,
    cluster_selection_method: str = "eom",
    cluster_selection_epsilon: float = 0.0,
    policy=None,
) -> HierarchyResult:
    """Vectorized condense/select/label for one mpts row of a LinkageRange.

    ``policy`` (an ``api.selection.SelectionPolicy``, duck-typed so core
    never imports the api layer) bundles the four selection knobs; when
    given it overrides the individual keyword arguments (its
    ``min_cluster_size=None`` falls through to the per-mpts default).
    """
    if policy is not None:
        cluster_selection_method = policy.method
        cluster_selection_epsilon = policy.epsilon
        allow_single_cluster = policy.allow_single_cluster
        if policy.min_cluster_size is not None:
            min_cluster_size = policy.min_cluster_size
    mpts = msts.mpts_values[row]
    mcs = min_cluster_size if min_cluster_size is not None else max(2, mpts)
    Z = linkage.linkage_to_Z(lk.left[row], lk.right[row], lk.height[row], lk.size[row])
    tree = hierarchy.condense_tree_fast(Z, msts.n, mcs)
    stab = hierarchy.compute_stability_fast(tree)
    selected = hierarchy.extract_clusters(
        tree,
        stab,
        allow_single_cluster=allow_single_cluster,
        cluster_selection_method=cluster_selection_method,
        cluster_selection_epsilon=cluster_selection_epsilon,
    )
    labels, lam_pt = hierarchy.labels_for_fast(tree, selected)
    return HierarchyResult(
        mpts=mpts,
        labels=labels,
        n_clusters=int(labels.max()) + 1,
        condensed=tree,
        stability=stab,
        mst_ea=msts.mst_ea[row].astype(np.int64),
        mst_eb=msts.mst_eb[row].astype(np.int64),
        mst_w=msts.mst_w[row],
        selected=selected,
        point_lambda=lam_pt,
    )


def extract_hierarchies(
    msts: MultiMSTResult,
    *,
    lk: LinkageRange | None = None,
    min_cluster_size: int | None = None,
    allow_single_cluster: bool = False,
    cluster_selection_method: str = "eom",
    cluster_selection_epsilon: float = 0.0,
    policy=None,
) -> tuple[list[HierarchyResult], dict[str, float]]:
    """Batched extraction of the whole range; returns (hierarchies, timings).

    ``hierarchy_linkage`` and ``hierarchy_condense`` are the ``extract.*``
    spans' seconds (``engine.trace``); a given ``lk`` costs no linkage.
    """
    with trace.recording() as rec:
        if lk is None:
            lk = linkage_range(msts)
        out = [
            extract_one_from_linkage(
                msts,
                lk,
                row,
                min_cluster_size=min_cluster_size,
                allow_single_cluster=allow_single_cluster,
                cluster_selection_method=cluster_selection_method,
                cluster_selection_epsilon=cluster_selection_epsilon,
                policy=policy,
            )
            for row in range(len(msts.mpts_values))
        ]
    seconds = rec.seconds()
    timings = {
        "hierarchy_linkage": seconds.get("extract.linkage", 0.0),
        "hierarchy_condense": seconds.get("extract.condense", 0.0),
    }
    timings["hierarchy"] = timings["hierarchy_linkage"] + timings["hierarchy_condense"]
    return out, timings


def multi_hdbscan(
    x,
    kmax: int,
    *,
    kmin: int = 2,
    variant: str = "rng_star",
    min_cluster_size: int | None = None,
    allow_single_cluster: bool = False,
    cluster_selection_method: str = "eom",
    cluster_selection_epsilon: float = 0.0,
    backend: str | None = None,
    compute_hierarchies: bool = True,
    mpts_values: Sequence[int] | None = None,
    plan: "engine.Plan | str | None" = None,
) -> MultiDensityResult:
    """All HDBSCAN* hierarchies for mpts in [kmin, kmax] via one RNG^kmax."""
    _validate_min_cluster_size(min_cluster_size)
    msts = fit_msts(
        x, kmax, kmin=kmin, variant=variant, backend=backend,
        mpts_values=mpts_values, plan=plan,
    )
    timings = dict(msts.timings)
    hierarchies: list[HierarchyResult] = []
    if compute_hierarchies:
        hierarchies, t_extract = extract_hierarchies(
            msts,
            min_cluster_size=min_cluster_size,
            allow_single_cluster=allow_single_cluster,
            cluster_selection_method=cluster_selection_method,
            cluster_selection_epsilon=cluster_selection_epsilon,
        )
        timings.update(t_extract)
    else:
        timings["hierarchy"] = 0.0
    timings["total"] = sum(timings[k] for k in FIT_STAGES) + timings["hierarchy"]

    return MultiDensityResult(
        n=msts.n,
        kmax=kmax,
        mpts_values=msts.mpts_values,
        graph=msts.graph,
        knn_d2=msts.knn_d2,
        knn_idx=msts.knn_idx,
        cd2=msts.cd2,
        hierarchies=hierarchies,
        timings=timings,
    )


def hdbscan_baseline(
    x,
    mpts_values: Sequence[int],
    *,
    kmax: int | None = None,
    min_cluster_size: int | None = None,
    allow_single_cluster: bool = False,
    cluster_selection_method: str = "eom",
    cluster_selection_epsilon: float = 0.0,
    backend: str | None = None,
    compute_hierarchies: bool = True,
    plan: "engine.Plan | str | None" = None,
) -> tuple[list[HierarchyResult], dict[str, float]]:
    """Paper's baseline: shared kNN pass + dense complete-graph MST per mpts."""
    _validate_min_cluster_size(min_cluster_size)
    plan = plan if isinstance(plan, engine.Plan) else engine.resolve_plan(plan, backend=backend)
    x_host = engine.io.ensure_host(x)
    x = jnp.asarray(x_host)
    n = x.shape[0]
    mpts_list = list(mpts_values)
    kmax = kmax or max(mpts_list)

    eb = np.arange(1, n, dtype=np.int32)
    mst_ea = np.zeros((len(mpts_list), n - 1), np.int32)
    mst_w = np.zeros((len(mpts_list), n - 1), np.float32)
    results: list[HierarchyResult] = []
    with trace.recording() as rec:
        with trace.span("knn"):
            knn_d2, _ = plan.knn(x, kmax - 1, x_host=x_host)
            cd2 = mrd_mod.core_distances2(knn_d2)
            cd2.block_until_ready()
        for row, mpts in enumerate(mpts_list):
            with trace.span("baseline.mst"):
                src, w2 = boruvka.prim_dense_mst(x, cd2[:, mpts - 1])
                w2.block_until_ready()
            src_h, w2_h = engine.to_host((src, w2), "mst")
            mst_ea[row] = src_h[1:]
            mst_w[row] = np.sqrt(w2_h[1:])
        with trace.span("baseline.extract"):
            if compute_hierarchies:
                knn_d2_h, cd2_h = engine.to_host((knn_d2, cd2), "knn")
                msts = MultiMSTResult(
                    n=n,
                    kmax=kmax,
                    mpts_values=mpts_list,
                    graph=None,
                    knn_d2=knn_d2_h,
                    knn_idx=np.zeros((n, 0), np.int32),
                    cd2=cd2_h,
                    mst_ea=mst_ea,
                    mst_eb=np.broadcast_to(eb, mst_ea.shape),
                    mst_w=mst_w,
                    timings={},
                )
                results, _ = extract_hierarchies(
                    msts,
                    min_cluster_size=min_cluster_size,
                    allow_single_cluster=allow_single_cluster,
                    cluster_selection_method=cluster_selection_method,
                    cluster_selection_epsilon=cluster_selection_epsilon,
                )
    seconds = rec.seconds()
    timings = {
        "knn": seconds["knn"],
        "mst": seconds.get("baseline.mst", 0.0),
        "hierarchy": seconds["baseline.extract"],
    }
    timings["total"] = timings["knn"] + timings["mst"] + timings["hierarchy"]
    return results, timings
