"""Trace specs: every compiled program family at one representative shape.

Each spec binds a family name to (a) a builder returning the traceable
callable plus abstract operands (``jax.ShapeDtypeStruct`` — no real data is
ever allocated), (b) the ``engine.io`` ledger tags whose materialization
points the family's results cross, and (c) the expected donation set.  The
jaxpr checks (``trace.py``) and the HLO cost manifest (``manifest.py``) both
iterate this table, so adding a compiled family to the pipeline without
listing it here fails the manifest drift gate in CI.

The representative configuration mirrors the tracked benchmark
(BENCH_pipeline.json): n=4000, d=8, kmax=16, R=15 mpts rows, and the
m_pad=45056 edge bucket the n=4000 RNG lands in (44036 real edges rounded
to the 4096-multiple ladder of ``core.multi.fit_msts``).  Registry families
(``plan.cached_program``) are traced at their representative bucket; the
remaining families are plain jitted programs whose shape discipline comes
from caller-side padding.

The mesh/ring families (``dist.cluster_parallel``) are NOT listed: they
need a multi-device mesh to trace and their per-shard bodies are the same
single-device programs audited here.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable

# representative configuration (matches BENCH_pipeline.json)
N = 4000          # points
D = 8             # dimensionality
KMAX = 16         # mpts upper bound
R = KMAX - 1      # mpts rows (2..kmax inclusive -> 15)
KQ = KMAX - 1     # query-kNN depth (kmax - 1)
SLACK = 8         # Plan.knn_refine_slack
M_PAD = 45056     # edge bucket at n=4000 (44036 real -> 11 * 4096)
Q_PAD = 64        # smallest predict query bucket
KEYS_PAD = 1 << 18  # SBCN packed-key sort block
CHUNK = 65536     # Plan.cascade_chunk


@dataclasses.dataclass(frozen=True)
class TraceSpec:
    """One compiled program family, abstractly traceable."""

    name: str
    build: Callable[[], tuple]   # () -> (fn, args tuple of ShapeDtypeStruct)
    sync_tags: tuple[str, ...]   # io ledger tags downstream of this program
    registry: bool = False       # True = a cached_program family
    donate: tuple[int, ...] = () # expected donated flat-arg positions


def _sds(shape, dtype):
    import jax

    return jax.ShapeDtypeStruct(shape, dtype)


def _f32(*shape):
    import jax.numpy as jnp

    return _sds(shape, jnp.float32)


def _i32(*shape):
    import jax.numpy as jnp

    return _sds(shape, jnp.int32)


def _bool(*shape):
    import jax.numpy as jnp

    return _sds(shape, jnp.bool_)


# -- builders (lazy: importing this module must not import jax) -------------


def _knn_self():
    from repro.kernels import ops

    k_eff = min(N - 1, (KMAX - 1) + SLACK)
    fn = functools.partial(ops._knn_jnp_blocked, k_top=k_eff)
    return fn, (_f32(N, D),)


def _knn_refine():
    from repro.kernels import ops

    k_eff = min(N - 1, (KMAX - 1) + SLACK)
    fn = functools.partial(ops._refine_knn, k_top=KMAX - 1)
    return fn, (_f32(N, D), _f32(N, D), _i32(N, k_eff))


def _query_knn():
    from repro.kernels import ops

    k_eff = min(N, KQ + SLACK)
    fn = functools.partial(ops._query_knn_blocked, k_top=k_eff)
    return fn, (_f32(Q_PAD, D), _f32(N, D))


def _lune_exact():
    from repro.kernels import ops

    m = 4096
    return ops._lune_jnp, (
        _i32(m), _i32(m), _f32(m), _f32(N, D), _f32(N),
    )


def _cascade_stage1():
    from repro.kernels import fused_cascade as fc

    fn = functools.partial(fc.stage1_packed, k_check=2, chunk=CHUNK)
    return fn, (
        _f32(N, D), _f32(N), _i32(N, KMAX - 1), _f32(N, KMAX - 1),
        _i32(KEYS_PAD), _i32(),
    )


def _cascade_stage2():
    from repro.kernels import fused_cascade as fc

    fn = functools.partial(fc._edge_cascade_jnp, k_check=KMAX - 1, chunk=CHUNK)
    return fn, (
        _f32(N, D), _f32(N), _i32(N, KMAX - 1), _f32(N, KMAX - 1),
        _i32(CHUNK), _i32(CHUNK), _bool(CHUNK),
    )


def _tier_emit():
    from repro.core import sbcn

    kaa, kbb = 4, 8   # largest small tier; chunk is a pure function of it
    chunk = max(8, sbcn._TIER_CHUNK_ELEMS // (kaa * kbb))
    fn = functools.partial(sbcn._tier_emit, tie_cap=3)
    return fn, (_f32(N, D), _f32(N), _i32(chunk, kaa), _i32(chunk, kbb), _i32())


def _rowpath_emit():
    from repro.core import sbcn

    # smallest row-chunked bucket: with nc == 1 the program is the tier
    # body (_mutual_mask + _emit_from_mask), which the tier_emit spec traces
    rc, nc, nb = 256, 2, 512
    pb = sbcn._rowpath_block(rc, nc, nb)
    fn = functools.partial(sbcn._rowpath_emit, tie_cap=3)
    return fn, (_f32(N, D), _f32(N), _i32(pb, nc, rc), _i32(pb, nb), _i32())


def _sort_dedup():
    from repro.core import sbcn

    return sbcn._sort_dedup_stats, (_i32(KEYS_PAD),)


def _edge_weights():
    from repro.core import rng

    fn = functools.partial(rng._edge_weights_chunked, chunk=rng._WEIGHT_CHUNK)
    return fn, (_f32(N, D), _f32(N), _i32(M_PAD), _i32(M_PAD))


def _mst_stage_local():
    from repro.core import multi

    fn = functools.partial(multi._mst_stage_local.__wrapped__, n=N)
    return fn, (_f32(M_PAD), _f32(N, KMAX), _i32(M_PAD), _i32(M_PAD), _i32(R))


def _boruvka_range():
    from repro.core import boruvka

    fn = functools.partial(boruvka._boruvka_mst_range, n=N)
    return fn, (_i32(M_PAD), _i32(M_PAD), _f32(R, M_PAD))


def _linkage_batch():
    from repro.core import linkage

    fn = functools.partial(linkage.single_linkage_batch.__wrapped__, n=N)
    return fn, (_i32(R, N - 1), _i32(R, N - 1), _f32(R, N - 1))


def _predict_attach():
    from repro.core import predict

    fn = predict._build_attach(Q_PAD, KQ, KMAX, R)
    return fn, (_f32(Q_PAD, KQ), _i32(Q_PAD, KQ), _f32(Q_PAD, KQ, KMAX), _i32(R))


def _prim_dense():
    from repro.core import boruvka

    return boruvka.prim_dense_mst, (_f32(N, D), _f32(N))


SPECS: tuple[TraceSpec, ...] = (
    TraceSpec("knn_self", _knn_self, ("knn",)),
    TraceSpec("knn_refine", _knn_refine, ("knn",)),
    TraceSpec("query_knn", _query_knn, ("predict",)),
    TraceSpec("lune_exact", _lune_exact, ("lune_exact",)),
    TraceSpec("cascade_stage1", _cascade_stage1, ("stage1_count", "graph")),
    TraceSpec("cascade_stage2", _cascade_stage2, ("graph",)),
    TraceSpec("tier_emit", _tier_emit, ("candidate_count",), registry=True),
    TraceSpec("rowpath_emit", _rowpath_emit, ("candidate_count",), registry=True),
    TraceSpec("sort_dedup", _sort_dedup, ("candidate_count", "candidate_slots")),
    TraceSpec(
        "edge_weights_canonical", _edge_weights, ("graph",), registry=True
    ),
    TraceSpec("mst_stage_local", _mst_stage_local, ("mst",)),
    TraceSpec("boruvka_mst_range", _boruvka_range, ("mst",)),
    TraceSpec("linkage_batch", _linkage_batch, ("linkage",)),
    TraceSpec("predict_attach", _predict_attach, ("predict",), registry=True),
    TraceSpec("prim_dense_mst", _prim_dense, ("mst",)),
)


def spec_names() -> list[str]:
    return [s.name for s in SPECS]
