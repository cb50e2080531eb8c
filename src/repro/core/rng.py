"""RNG construction pipeline: RNG** -> RNG* -> exact RNG (paper §IV-E, Alg. 1).

Variants (paper's naming):
  * ``rng_ss``  (RNG**): WSPD+SBCN supergraph, no filtering (Alg. 1 line 12).
  * ``rng_star`` (RNG*): + the 2*kmax-check filter using each endpoint's
    kmax-NN list, plus the core-distance certificate for definite keeps
    (lines 13-21).  May keep some non-RNG edges.
  * ``rng``     (exact): + full-dataset lune scan for edges the cheap filter
    could not certify either way (lines 22-26) — the Pallas ``lune_filter``
    kernel / its jnp twin / the mesh ring collective, per plan.

All predicates run in squared space (see core.mrd).

Two device data-planes build the filtered graph:

  * the FUSED CASCADE (default): bounded per-row candidate emission
    (``sbcn.cascade_candidates`` — packed int32 keys, single-key dedup
    sort), then the staged ``plan.edge_cascade`` programs: a ``stage1_k``
    lune prefilter kills ~90% of candidates before the full kmax-list
    check + core-distance certificate run on the survivors.  Staging and
    bounded emission are exact (see kernels.fused_cascade / core.sbcn);
    when emission detects a per-row tie overflow (mass-duplicate inputs)
    the build transparently falls back to
  * the SLOT-ARRAY path (``sbcn_candidates`` + ``filter_cascade_device``):
    dense per-cell slots, scatter compaction, unstaged kNN-lune check.
    Retained as the golden reference (tests pin the fused path's edge sets
    against it), as the ``backend="ref"`` path, and for n too large to pack
    (lo, hi) into int32 keys.

Dataflow: the WSPD tree and pair recursion are host control-plane (numpy);
everything else is device-resident jax programs over padded/masked arrays.
Host syncs are the named ledger points only: ``candidate_count`` /
``stage1_count`` (scalars sizing the static compactions), ``graph`` (the
one bulk materialization), and ``lune_exact`` for the exact variant.
Each host stage is an ``engine.trace`` span: ``rng.wspd`` (tree, pairs
and their point ranges), ``rng.emit`` (candidate emission),
``rng.cascade`` (the filter stages' dispatch), ``rng.graph_host`` (edge
assembly after the ``graph`` sync) and, on the large-n tier,
``rng.dualtree``.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from .. import engine
from ..engine import trace
from . import dualtree as dualtree_mod
from . import mrd as mrd_mod
from . import sbcn as sbcn_mod
from . import wspd as wspd_mod

VARIANTS = ("rng_ss", "rng_star", "rng")

# the fused path packs (lo, hi) as lo * n + hi into int32 keys
_PACK_LIMIT = 46340


def _pow2_ceil(v: int) -> int:
    return 1 << max(0, int(v - 1).bit_length())


@dataclasses.dataclass
class RngGraph:
    """The single precomputed graph that serves the whole mpts range."""

    edges: np.ndarray      # (m, 2) int64, a < b
    d2: np.ndarray         # (m,)  squared Euclidean edge lengths
    w2_kmax: np.ndarray    # (m,)  squared mrd_kmax weights
    variant: str
    n_points: int
    stats: dict


@functools.partial(jax.jit, static_argnames=("chunk",))
def _knn_lune_check(x, cd2k, knn_idx, knn_d2, ea, eb, w2, *, chunk: int = 16384):
    """Paper lines 14-17: is any kmax-NN of a or b strictly inside lune(a,b)?

    Tie robustness: mrd ties are STRUCTURAL here (e.g. c is b's kmax-th
    neighbor => mrd(b,c) = cd(b) = mrd(a,b) exactly in real arithmetic), and
    f32 noise — including XLA's per-callsite FMA contraction, which makes
    even identical formulas differ by ulps across call sites — must never
    flip a tie into a removal.  Two defenses: (1) own-list distances are read
    from the stored kNN pass instead of recomputed, making the most common
    tie bit-exact; (2) a norm-scaled epsilon margin is added on the "inside"
    side, so residual noise can only KEEP an edge (the superset-safe
    direction), mirroring the exact-filter kernel.

    Returns (m,) bool `inside_any`.
    """
    eps = jnp.float32(64.0 * 1.1920929e-07)

    def one_chunk(args):
        ea_c, eb_c, w2_c = args
        cand_a = knn_idx[ea_c]                                           # (c, k)
        cand_b = knn_idx[eb_c]
        xa = x[ea_c].astype(jnp.float32)
        xb = x[eb_c].astype(jnp.float32)
        xca = x[cand_a].astype(jnp.float32)                              # (c, k, d)
        xcb = x[cand_b].astype(jnp.float32)
        # own-list distances come from storage; cross distances are recomputed
        d2a_ca = knn_d2[ea_c]                                            # d2(a, cand_a)
        d2b_cb = knn_d2[eb_c]                                            # d2(b, cand_b)
        d2b_ca = jnp.sum((xb[:, None, :] - xca) ** 2, -1)                # d2(b, cand_a)
        d2a_cb = jnp.sum((xa[:, None, :] - xcb) ** 2, -1)                # d2(a, cand_b)

        cda = cd2k[ea_c][:, None]
        cdb = cd2k[eb_c][:, None]
        an = jnp.sum(xa * xa, -1)[:, None]
        bn = jnp.sum(xb * xb, -1)[:, None]

        def inside(cand, xc, d2ac, d2bc):
            cdc = cd2k[cand]
            cn = jnp.sum(xc * xc, -1)
            mrd_ac = jnp.maximum(jnp.maximum(d2ac, cda), cdc) + eps * (an + cn)
            mrd_bc = jnp.maximum(jnp.maximum(d2bc, cdb), cdc) + eps * (bn + cn)
            not_ep = (cand != ea_c[:, None]) & (cand != eb_c[:, None])
            return jnp.any(
                (jnp.maximum(mrd_ac, mrd_bc) < w2_c[:, None]) & not_ep, axis=1
            )

        return inside(cand_a, xca, d2a_ca, d2b_ca) | inside(cand_b, xcb, d2a_cb, d2b_cb)

    m = ea.shape[0]
    m_pad = -(-m // chunk) * chunk
    pad = lambda v, f: jnp.concatenate(  # noqa: E731
        [v, jnp.full((m_pad - m,), f, v.dtype)]
    )
    ea_p, eb_p = pad(ea, 0), pad(eb, 0)
    w2_p = pad(w2, -jnp.inf)  # padded edges can never have points inside
    res = jax.lax.map(
        one_chunk,
        (
            ea_p.reshape(-1, chunk),
            eb_p.reshape(-1, chunk),
            w2_p.reshape(-1, chunk),
        ),
    )
    return res.reshape(m_pad)[:m]


def filter_cascade_device(
    x: jax.Array,
    cd2: jax.Array,
    knn_idx: jax.Array,
    knn_d2: jax.Array,
    lo: jax.Array,
    hi: jax.Array,
    valid: jax.Array,
    *,
    plan: engine.Plan,
):
    """Device filter cascade over padded/masked candidate slots.

    Returns device arrays ``(keep, certified, inside_any, d2_e, w2)`` — keep
    is the RNG* verdict (valid & not removed by the kNN-lune check);
    certified marks edges provably in the exact RNG (w == max core dist).
    Nothing is materialized; invalid slots read index 0 and are masked.
    """
    cd2k = cd2[:, -1]
    ea = jnp.where(valid, lo, 0).astype(jnp.int32)
    eb = jnp.where(valid, hi, 0).astype(jnp.int32)
    d2_e = mrd_mod.edge_d2(x, ea, eb)
    w2 = mrd_mod.mrd2_from_parts(d2_e, cd2k[ea], cd2k[eb])
    inside_any = _knn_lune_check(
        x, cd2k, knn_idx, knn_d2, ea, eb, w2, chunk=plan.filter_chunk
    ) & valid
    # core-distance certificate: w == max(c(a), c(b))  =>  definitely in RNG
    # lint: allow[float-eq] bit-exact by construction: w2 = max(max(ca,cb), d2) so equality with max(ca,cb) round-trips unchanged (pinned by tests/test_float_discipline.py)
    certified = (w2 == jnp.maximum(cd2k[ea], cd2k[eb])) & valid
    keep = valid & ~inside_any
    return keep, certified, inside_any, d2_e, w2


def _exact_lune_pass(keep, certified, ea_h, eb_h, w2_h, x, cd2k, plan, stats):
    """variant="rng" (Alg. 1 lines 22-26): exact lune scan of the edges the
    cheap filter could not certify either way.  Mutates ``stats``; returns
    the updated keep mask (host bool array)."""
    unresolved = keep & ~certified
    stats["m_unresolved"] = int(unresolved.sum())
    if not unresolved.any():
        return keep
    keep = keep.copy()  # device_get views are read-only
    ui = np.nonzero(unresolved)[0]
    nonempty = engine.to_host(
        plan.lune_nonempty(
            jnp.asarray(ea_h[ui], jnp.int32),
            jnp.asarray(eb_h[ui], jnp.int32),
            jnp.asarray(w2_h[ui]),
            x,
            cd2k,
        ),
        "lune_exact",
    )
    keep[ui[nonempty]] = False
    stats["m_removed_exact"] = int(nonempty.sum())
    return keep


def filter_edges(
    x: jax.Array,
    cd2: jax.Array,
    knn_idx: jax.Array,
    knn_d2: jax.Array,
    edges: np.ndarray,
    variant: str,
    *,
    backend: str | None = None,
    plan: engine.Plan | None = None,
) -> tuple[np.ndarray, dict]:
    """Apply the paper's filter cascade to an explicit (m, 2) edge array.

    Compatibility wrapper over ``filter_cascade_device`` for host edge lists;
    returns (kept edge array, stats dict).
    """
    if variant not in VARIANTS:
        raise ValueError(f"variant must be one of {VARIANTS}")
    plan = engine.resolve_plan(plan, backend=backend) if not isinstance(plan, engine.Plan) else plan
    stats = {"m_candidates": int(len(edges))}
    if variant == "rng_ss" or len(edges) == 0:
        return edges, stats

    lo = jnp.asarray(edges[:, 0], jnp.int32)
    hi = jnp.asarray(edges[:, 1], jnp.int32)
    valid = jnp.ones((len(edges),), bool)
    keep_d, certified_d, inside_d, _, w2_d = filter_cascade_device(
        x, cd2, knn_idx, knn_d2, lo, hi, valid, plan=plan
    )
    keep, certified, inside_any, w2 = engine.to_host(
        (keep_d, certified_d, inside_d, w2_d), "graph"
    )
    stats["m_removed_knn"] = int(inside_any.sum())
    stats["m_certified"] = int((keep & certified).sum())

    if variant == "rng":
        keep = _exact_lune_pass(
            keep, certified, edges[:, 0], edges[:, 1], w2, x, cd2[:, -1], plan, stats
        )
    return edges[keep], stats


def _empty_graph(variant: str, n: int, n_pairs: int) -> RngGraph:
    return RngGraph(
        edges=np.zeros((0, 2), np.int64),
        d2=np.zeros((0,), np.float32),
        w2_kmax=np.zeros((0,), np.float32),
        variant=variant,
        n_points=n,
        stats={"m_candidates": 0, "n_wspd_pairs": n_pairs, "m_edges": 0},
    )


@jax.jit
def _unpack_keys(ks, n_pack):
    """Sorted packed keys -> (valid, first-occurrence, lo, hi)."""
    valid = ks != sbcn_mod._SENTINEL
    first = jnp.concatenate([jnp.ones((1,), bool), ks[1:] != ks[:-1]])
    safe = jnp.where(valid, ks, 0)
    return valid, first, (safe // n_pack).astype(jnp.int32), (safe % n_pack).astype(jnp.int32)


@jax.jit
def _split_survivors(valid, first, killed1, cert1):
    """Stage-1 verdicts -> (certified survivors, open survivors, counts)."""
    surv = valid & first & ~killed1
    sc = surv & cert1
    so = surv & ~cert1
    return sc, so, jnp.sum(sc), jnp.sum(so)


@functools.partial(jax.jit, static_argnames=("cap",))
def _compact_idx(mask, *, cap: int):
    return jnp.nonzero(mask, size=cap, fill_value=0)[0]


def _build_fused(
    x, cd2, knn_d2, knn_idx, perm, pairs, variant, plan
) -> RngGraph | None:
    """Fused-cascade RNG build; returns None on tie overflow (caller falls
    back to the slot-array path, which keeps ALL tied SBCN minima).

    ``pairs`` is the WSPD pair list as (a_start, a_len, b_start, b_len)
    ranges of the fair-split tree's ``perm``."""
    n = x.shape[0]
    n_pairs = len(pairs[0])
    cd2k = cd2[:, -1]
    with trace.recording() as emitted:
        keys_sorted, n_real_d, n_unique_d, n_mutual_d, n_overflow_d = (
            sbcn_mod.cascade_candidates(
                x,
                cd2k,
                perm,
                *pairs,
                tie_cap=plan.cascade_tie_cap,
                tier_chunk_elems=plan.tier_chunk_elems,
            )
        )
    emit_counts = emitted.counters()
    # ONE scalar sync sizes the stage-1 buffer and reports the exact tie
    # overflow verdict (no silent edge drops — overflow means fall back)
    n_real, n_unique, n_mutual, n_overflow = (
        int(v)
        for v in engine.to_host(
            (n_real_d, n_unique_d, n_mutual_d, n_overflow_d), "candidate_count"
        )
    )
    if n_overflow:
        return None
    if n_real == 0:
        return _empty_graph(variant, n, n_pairs)

    cap = min(_pow2_ceil(n_real), keys_sorted.shape[0])
    ks = keys_sorted[:cap]
    n_pack = jnp.int32(n)

    stats = {
        "m_candidates": n_unique,
        "n_wspd_pairs": n_pairs,
        "m_candidate_slots": n_real,
        "m_mutual_slots": n_mutual,
        "emit_dispatches": int(
            emit_counts.get("emit.tier_dispatches", 0)
            + emit_counts.get("emit.rowpath_dispatches", 0)
        ),
        "emit_upload_bytes": int(emit_counts.get("emit.upload_bytes", 0)),
        "path": "fused",
    }

    with trace.span("rng.cascade"):
        # stage 1: cheap prefilter over each endpoint's k1 nearest — its
        # removals are a subset of the full check's, so survivors-only stage 2
        # gives the identical final verdict for a fraction of the work.  The
        # core-distance certificate splits the survivors further: a certified
        # edge has w == max(cd(a), cd(b)) and every lune competitor c satisfies
        # mrd(a,c) >= cd(a) and mrd(b,c) >= cd(b) (max is exact in f32), so
        # nothing can ever lie strictly inside its lune — certified survivors
        # skip the full check entirely.
        k_full = knn_idx.shape[1]
        k1 = min(plan.cascade_stage1_k, k_full)
        if plan.backend in ("pallas", "pallas_interpret"):
            valid, first, lo, hi = _unpack_keys(ks, n_pack)
            killed1, cert1, d2_1, w2_1 = plan.edge_cascade(
                x, cd2k, knn_idx, knn_d2, lo, hi, valid, k_check=k1
            )
            surv_cert, surv_open, nc_d, no_d = _split_survivors(
                valid, first, killed1, cert1
            )
        else:
            # jnp backends: the whole stage-1 block is one program
            from ..kernels import fused_cascade as fc

            lo, hi, d2_1, w2_1, surv_cert, surv_open, nc_d, no_d = fc.stage1_packed(
                x, cd2k, knn_idx, knn_d2, ks, n_pack,
                k_check=k1, chunk=plan.cascade_chunk,
            )
        n_cert, n_open = (
            int(v) for v in engine.to_host((nc_d, no_d), "stage1_count")
        )
        if n_cert + n_open == 0:
            return _empty_graph(variant, n, n_pairs)

        q = 8192  # survivor caps quantized to coarse blocks: few programs, <12% pad
        parts_dev = []
        if n_cert:
            capc = min(_pow2_ceil(n_cert), -(-n_cert // q) * q)
            posc = _compact_idx(surv_cert, cap=capc)
            validc = jnp.arange(capc) < n_cert
            keepc = validc  # certified => provably in the exact RNG
            d2c, w2c = canonical_edge_weights(x, cd2k, lo[posc], hi[posc])
            parts_dev.append(
                (lo[posc], hi[posc], keepc, validc, d2c, w2c, w2_1[posc])
            )
        if n_open:
            capo = min(_pow2_ceil(n_open), -(-n_open // q) * q)
            poso = _compact_idx(surv_open, cap=capo)
            valido = jnp.arange(capo) < n_open
            killed2, _, d2_2, w2_2 = plan.edge_cascade(
                x, cd2k, knn_idx, knn_d2, lo[poso], hi[poso], valido,
                k_check=k_full,
            )
            d2o, w2o = canonical_edge_weights(x, cd2k, lo[poso], hi[poso])
            parts_dev.append(
                (lo[poso], hi[poso], valido & ~killed2, jnp.zeros_like(valido),
                 d2o, w2o, w2_2)
            )

    parts = engine.to_host(parts_dev, "graph")
    with trace.span("rng.graph_host"):
        lo_h = np.concatenate([p[0] for p in parts])
        hi_h = np.concatenate([p[1] for p in parts])
        keep = np.concatenate([p[2] for p in parts])
        certified = np.concatenate([p[3] for p in parts])
        d2_h = np.concatenate([p[4] for p in parts])
        w2_h = np.concatenate([p[5] for p in parts])
        w2_stage = np.concatenate([p[6] for p in parts])
        # restore the slot path's sorted-(lo, hi) edge order: downstream MST
        # tie-breaks are by edge id, so order parity keeps the paths bit-equal
        order = np.lexsort((hi_h, lo_h))
        lo_h, hi_h, keep, certified, d2_h, w2_h, w2_stage = (
            v[order] for v in (lo_h, hi_h, keep, certified, d2_h, w2_h, w2_stage)
        )
        stats["m_removed_knn"] = n_unique - int(keep.sum())
        stats["m_certified"] = int((keep & certified).sum())

        if variant == "rng":
            # the lune pass thresholds on the STAGE w2 values (their verdicts
            # carry the eps margins); the exported arrays stay canonical
            keep = _exact_lune_pass(
                keep, certified, lo_h, hi_h, w2_stage, x, cd2k, plan, stats
            )

        edges = np.stack(
            [lo_h[keep].astype(np.int64), hi_h[keep].astype(np.int64)], axis=1
        )
        stats["m_edges"] = int(len(edges))
        return RngGraph(
            edges=edges,
            d2=d2_h[keep],
            w2_kmax=w2_h[keep],
            variant=variant,
            n_points=n,
            stats=stats,
        )


# ---------------------------------------------------------------------------
# Canonical per-edge weight program
# ---------------------------------------------------------------------------
#
# The d2/w2_kmax arrays EXPORTED on RngGraph feed the all-mpts reweight and
# therefore every MST weight downstream.  XLA codegen is only bitwise
# deterministic within one compiled program: the same diff-form formula
# inlined into the fused-cascade programs, the eager slot-path ops and a
# separate jitted helper can disagree by ulps (per-callsite FMA contraction,
# shape-dependent vectorization), which breaks the bit-parity contract
# between candidate paths that produce the same edge set.  So every path
# exports through THIS one program: a fixed (chunk,)-shaped lax.map body,
# shared via the cached_program registry — identical program, identical
# operand shapes, identical bits, for any edge count.  The filter stages
# keep using their own in-program values (their verdicts carry eps margins
# that absorb ulp noise); only the exported arrays are canonicalized.

_WEIGHT_CHUNK = 4096

# One program, ever: the chunk is a module constant and d is configuration.
engine.plan.declare_family(
    "edge_weights_canonical",
    key_schema=("chunk", "d"),
    bucket_dims=("chunk",),
    max_programs=1,
)


@functools.partial(jax.jit, static_argnames=("chunk",))
def _edge_weights_chunked(x, cd2k, ea, eb, *, chunk: int):
    def one(args):
        ea_c, eb_c = args
        d2 = mrd_mod.edge_d2(x, ea_c, eb_c)
        return d2, mrd_mod.mrd2_from_parts(d2, cd2k[ea_c], cd2k[eb_c])

    d2, w2 = jax.lax.map(
        one, (ea.reshape(-1, chunk), eb.reshape(-1, chunk))
    )
    return d2.reshape(-1), w2.reshape(-1)


def canonical_edge_weights(x, cd2k, ea, eb):
    """Exact f32 (d2, w2_kmax) for an edge list — the one export program.

    Pads to the fixed chunk multiple (index-0 edges, sliced back off), so
    the compiled body sees one shape regardless of m and two calls on the
    same (n, d) dataset agree bitwise edge-for-edge.
    """
    m = int(ea.shape[0])
    m_pad = -(-max(m, 1) // _WEIGHT_CHUNK) * _WEIGHT_CHUNK
    ea = jnp.asarray(ea, jnp.int32)
    eb = jnp.asarray(eb, jnp.int32)
    if m_pad != m:
        pad = jnp.zeros((m_pad - m,), jnp.int32)
        ea = jnp.concatenate([ea, pad])
        eb = jnp.concatenate([eb, pad])
    prog = engine.plan.cached_program(
        ("edge_weights_canonical", _WEIGHT_CHUNK, int(x.shape[1])),
        lambda: functools.partial(_edge_weights_chunked, chunk=_WEIGHT_CHUNK),
    )
    d2, w2 = prog(x, cd2k, ea, eb)
    return d2[:m], w2[:m]


def _build_dualtree(
    x, knn_d2, knn_idx, variant, plan, x_host, knn_d2_host, knn_idx_host
) -> RngGraph:
    """Large-n tier: dual-tree Borůvka candidate edges + device weights.

    The host traversals select edge STRUCTURE only (core.dualtree); the d2
    and w2_kmax values that reach results come from the canonical per-edge
    weight program every tier exports through, in one ``graph`` sync.  The
    graph is kNN^kmax ∪ S with S ⊇ an MST under mrd_kmax — a strict
    superset of what every per-mpts MST needs (see core.dualtree), though
    NOT an RNG: the ``variant`` filter semantics don't apply on this tier.
    """
    n = x.shape[0]
    with trace.span("rng.dualtree"):
        edges, stats = dualtree_mod.candidate_edges(
            x_host,
            knn_d2_host,
            knn_idx_host,
            leaf_size=plan.dualtree_leaf,
            margin=plan.dualtree_margin,
        )
    stats["path"] = "dualtree"
    m = len(edges)
    stats["m_edges"] = m
    if m == 0:
        return _empty_graph(variant, n, 0)
    d2_d, w2_d = canonical_edge_weights(
        x,
        knn_d2[:, -1],
        jnp.asarray(edges[:, 0], jnp.int32),
        jnp.asarray(edges[:, 1], jnp.int32),
    )
    d2_h, w2_h = engine.to_host((d2_d, w2_d), "graph")
    return RngGraph(
        edges=edges,
        d2=d2_h,
        w2_kmax=w2_h,
        variant=variant,
        n_points=n,
        stats=stats,
    )


def build_rng_graph(
    x: jax.Array,
    knn_d2: jax.Array,
    knn_idx: jax.Array,
    *,
    variant: str = "rng_star",
    separation: float = 1.0,
    backend: str | None = None,
    plan: engine.Plan | None = None,
    x_host: np.ndarray | None = None,
    cd_kmax_host: np.ndarray | None = None,
    knn_d2_host: np.ndarray | None = None,
    knn_idx_host: np.ndarray | None = None,
) -> RngGraph:
    """End-to-end candidate graph construction (Alg. 1 lines 5-29).

    knn_d2/knn_idx: the single (kmax-1)-NN pass (ascending squared distances).
    ``x_host`` / ``cd_kmax_host`` / ``knn_*_host`` feed the host control
    planes without a device sync when the caller already holds host views
    (fit_msts does); left None they are materialized here under the
    ``input`` tag.

    Size-tier dispatch (``plan.use_dualtree``): large n routes to the
    dual-tree Borůvka candidate path (``core.dualtree``, stats
    ``path="dualtree"``); otherwise the WSPD/SBCN build below runs —
    fused cascade by default, slot-array path as fallback/oracle.
    """
    if variant not in VARIANTS:
        raise ValueError(f"variant must be one of {VARIANTS}")
    plan = plan if isinstance(plan, engine.Plan) else engine.resolve_plan(plan, backend=backend)
    n = x.shape[0]
    cd2 = mrd_mod.core_distances2(knn_d2)
    if x_host is None:
        x_host = engine.io.ensure_host(x)

    if n > 2 and plan.use_dualtree(int(n)):
        if knn_d2_host is None or knn_idx_host is None:
            knn_d2_host, knn_idx_host = (
                engine.io.ensure_host(knn_d2),
                engine.io.ensure_host(knn_idx),
            )
        return _build_dualtree(
            x, knn_d2, knn_idx, variant, plan, x_host, knn_d2_host, knn_idx_host
        )

    if cd_kmax_host is None:
        cd_kmax_host = np.sqrt(
            engine.io.ensure_host(cd2[:, -1]).astype(np.float64)
        )

    # -- host control plane: fair-split tree + well-separated pairs ---------
    with trace.span("rng.wspd"):
        tree = wspd_mod.build_fair_split_tree(
            np.asarray(x_host, np.float64), cd_kmax_host
        )
        pu, pv = wspd_mod.wspd_pairs(tree, s=separation)
        a_start, b_start = tree.start[pu], tree.start[pv]
        pairs = (a_start, tree.end[pu] - a_start, b_start, tree.end[pv] - b_start)
    n_pairs = len(pu)

    # -- fused cascade (default): bounded emission + staged fused filter ----
    if variant != "rng_ss" and plan.backend != "ref" and n <= _PACK_LIMIT:
        g = _build_fused(x, cd2, knn_d2, knn_idx, tree.perm, pairs, variant, plan)
        if g is not None:
            return g
        # per-row tie overflow (mass duplicates): the slot path below keeps
        # every tied SBCN minimum, so no candidate is lost

    # -- slot-array data plane: dense candidates + unstaged filter cascade --
    lo_s, hi_s, keep_s = sbcn_mod.sbcn_candidates(
        x,
        cd2[:, -1],
        tree.perm,
        *pairs,
        tile_elems=plan.sbcn_tile_elems,
        pair_cap=plan.sbcn_pair_cap,
        row_chunk=plan.sbcn_row_chunk,
    )
    # Compact the sparse candidate slots to ~m edges ON DEVICE.  The filter
    # cascade must run on the unique candidates, not the (much larger) slot
    # array; the only thing that crosses to the host here is the COUNT — one
    # int — which sizes the static nonzero buffer.
    m_cand = int(engine.to_host(jnp.sum(keep_s), "candidate_count"))
    if m_cand == 0:
        return RngGraph(
            edges=np.zeros((0, 2), np.int64),
            d2=np.zeros((0,), np.float32),
            w2_kmax=np.zeros((0,), np.float32),
            variant=variant,
            n_points=n,
            stats={"m_candidates": 0, "n_wspd_pairs": n_pairs, "m_edges": 0},
        )
    with trace.span("rng.cascade"):
        cap = -(-m_cand // 4096) * 4096  # quantized: reuses filter programs
        pos = jnp.nonzero(keep_s, size=cap, fill_value=0)[0]
        lo = lo_s[pos]
        hi = hi_s[pos]
        valid = jnp.arange(cap) < m_cand

        cd2k = cd2[:, -1]
        ea = jnp.where(valid, lo, 0).astype(jnp.int32)
        eb = jnp.where(valid, hi, 0).astype(jnp.int32)
        if variant == "rng_ss":
            keep_d = valid
            certified_d = inside_d = jnp.zeros_like(valid)
            w2_d = jnp.zeros((int(valid.shape[0]),), jnp.float32)
        else:
            keep_d, certified_d, inside_d, _, w2_d = filter_cascade_device(
                x, cd2, knn_idx, knn_d2, lo, hi, valid, plan=plan
            )
        # exported weights always come from the canonical program (the filter
        # verdicts above keep their own in-program values)
        d2c_d, w2c_d = canonical_edge_weights(x, cd2k, ea, eb)

    # -- the one graph materialization --------------------------------------
    lo_h, hi_h, valid_h, keep, certified, inside_any, d2_h, w2_h, w2_stage = (
        engine.to_host(
            (lo, hi, valid, keep_d, certified_d, inside_d, d2c_d, w2c_d, w2_d),
            "graph",
        )
    )
    with trace.span("rng.graph_host"):
        stats = {
            "m_candidates": int(valid_h.sum()),
            "n_wspd_pairs": n_pairs,
        }
        if variant != "rng_ss":
            stats["m_removed_knn"] = int(inside_any.sum())
            stats["m_certified"] = int((keep & certified).sum())

        if variant == "rng":
            keep = _exact_lune_pass(
                keep, certified, lo_h, hi_h, w2_stage, x, cd2[:, -1], plan, stats
            )

        edges = np.stack(
            [lo_h[keep].astype(np.int64), hi_h[keep].astype(np.int64)], axis=1
        )
        stats["m_edges"] = int(len(edges))
        return RngGraph(
            edges=edges,
            d2=d2_h[keep],
            w2_kmax=w2_h[keep],
            variant=variant,
            n_points=n,
            stats=stats,
        )
