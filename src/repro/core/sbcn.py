"""Symmetric Bichromatic Closest Neighbors over WSPD pairs (paper §IV-E, Fig 4).

For each well-separated pair (A, B), connect a in A and b in B iff b is a's
closest point in B AND a is b's closest point in A, w.r.t. ``mrd_kmax``.  The
union over all pairs is the RNG** supergraph.

Device data-plane: pairs are bucketed by padded (|A|, |B|) size class and each
size tier is ONE jitted device program — a fixed-shape (chunk, amax, bmax)
mrd tile + masked argmin, dispatched over the tier's chunks with the results
kept on device.  ``sbcn_candidates`` returns the whole candidate set as jax
arrays (``lo``/``hi`` endpoint arrays, lexicographically sorted, duplicates
masked out), so the downstream filter cascade can stay device-resident; the
``sbcn_edges`` wrapper is the host-compacted (m, 2) numpy view.

Tie-robustness: ALL tied row/column minima are kept (a superset of the
single-argmin SBCN), which preserves the RNG-superset property under
duplicate mrd values.

Oversized pairs (padded |A|*|B| above the bucket cap) are evaluated with a
row-chunked two-pass min-reduction: peak memory is O(row_chunk * |B|)
regardless of |A|.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..engine import trace

_PAIR_ELEM_CAP = 1 << 18  # max padded |A|*|B| handled by the batched path
_TILE_ELEMS = 1 << 22     # elements per tier-program chunk
_ROW_CHUNK = 2048         # row chunk for oversized pairs
_SENTINEL = np.int32(np.iinfo(np.int32).max)  # invalid / duplicate slot marker

_EPS = 64.0 * 1.1920929e-07


def _mutual_mask(x, cd2k, a_idx, b_idx):
    """SBCN mask for one padded bucket chunk.

    a_idx: (P, amax) int32 point ids padded with -1; likewise b_idx.
    Returns (P, amax, bmax) bool mask of SBCN edges.
    """
    xa = x[a_idx]                                  # (P, amax, d)
    xb = x[b_idx]
    an = jnp.sum(xa.astype(jnp.float32) ** 2, -1)
    bn = jnp.sum(xb.astype(jnp.float32) ** 2, -1)
    d2 = (
        an[:, :, None]
        + bn[:, None, :]
        - 2.0 * jnp.einsum("pad,pbd->pab", xa.astype(jnp.float32), xb.astype(jnp.float32))
    )
    d2 = jnp.maximum(d2, 0.0)
    mrd2 = jnp.maximum(jnp.maximum(cd2k[a_idx][:, :, None], cd2k[b_idx][:, None, :]), d2)
    invalid = (a_idx < 0)[:, :, None] | (b_idx < 0)[:, None, :]
    mrd2 = jnp.where(invalid, jnp.inf, mrd2)
    # Norm-scaled tolerance: near-ties (incl. matmul-form cancellation noise)
    # are ALL kept as mutual-nearest candidates — only ever adds edges.
    tol = jnp.float32(_EPS) * (an[:, :, None] + bn[:, None, :])
    row_min = jnp.min(mrd2, axis=2, keepdims=True)     # (P, amax, 1)
    col_min = jnp.min(mrd2, axis=1, keepdims=True)     # (P, 1, bmax)
    return (
        (mrd2 <= row_min + tol)
        & (mrd2 <= col_min + tol)
        & ~invalid
        & jnp.isfinite(mrd2)
    )


@jax.jit
def _sbcn_tier_chunk(x, cd2k, a_idx, b_idx):
    """One fixed-shape tier chunk -> flat (lo, hi) candidate slots.

    This is THE device program for a size tier: compiled once per
    (chunk, amax, bmax) shape, dispatched over the tier's chunks, outputs
    stay on device.  Non-edge slots hold the sentinel.
    """
    mutual = _mutual_mask(x, cd2k, a_idx, b_idx)
    ga = jnp.broadcast_to(a_idx[:, :, None], mutual.shape)
    gb = jnp.broadcast_to(b_idx[:, None, :], mutual.shape)
    lo = jnp.where(mutual, jnp.minimum(ga, gb), _SENTINEL)
    hi = jnp.where(mutual, jnp.maximum(ga, gb), _SENTINEL)
    return lo.reshape(-1), hi.reshape(-1)


@functools.partial(jax.jit, static_argnames=("row_chunk",))
def _sbcn_large(x, cd2k, a_idx, b_idx, *, row_chunk: int = _ROW_CHUNK):
    """Row-chunked SBCN for one oversized pair. a_idx (na,), b_idx (nb,).

    Two passes over row chunks of the (na, nb) mrd tile — pass 1 reduces the
    column minima, pass 2 re-evaluates each chunk against the global minima —
    so peak memory is O(row_chunk * nb) float32, never the full tile.
    Returns the (na, nb) bool mutual mask.
    """
    na, nb = a_idx.shape[0], b_idx.shape[0]
    rc = min(row_chunk, na)
    na_pad = -(-na // rc) * rc
    a_pad = jnp.full((na_pad,), -1, a_idx.dtype).at[:na].set(a_idx)

    xb = x[b_idx].astype(jnp.float32)
    cdb = cd2k[b_idx]
    bnorm = jnp.sum(xb * xb, -1)

    def mrd_chunk(ac):
        xa = x[ac].astype(jnp.float32)
        anorm = jnp.sum(xa * xa, -1)
        d2 = anorm[:, None] + bnorm[None, :] - 2.0 * xa @ xb.T
        m = jnp.maximum(jnp.maximum(cd2k[ac][:, None], cdb[None, :]), jnp.maximum(d2, 0.0))
        m = jnp.where((ac < 0)[:, None], jnp.inf, m)
        tol = jnp.float32(_EPS) * (anorm[:, None] + bnorm[None, :])
        return m, tol

    chunks = a_pad.reshape(-1, rc)

    def pass1(ac):
        m, _ = mrd_chunk(ac)
        return jnp.min(m, axis=0)                      # (nb,) partial col min

    col_min = jnp.min(jax.lax.map(pass1, chunks), axis=0)[None, :]

    def pass2(ac):
        m, tol = mrd_chunk(ac)
        row_min = jnp.min(m, axis=1, keepdims=True)
        return (m <= row_min + tol) & (m <= col_min + tol) & jnp.isfinite(m)

    mask = jax.lax.map(pass2, chunks).reshape(na_pad, nb)
    return mask[:na]


def _dedup_sorted(lo, hi):
    """Lexicographically sort (lo, hi) slots; mask duplicate / sentinel slots.

    Returns (lo, hi, keep): sorted endpoint arrays and a bool mask that is
    True exactly on the first occurrence of each real edge.
    """
    lo, hi = jax.lax.sort((lo, hi), dimension=0, num_keys=2)
    valid = lo != _SENTINEL
    first = jnp.concatenate(
        [jnp.ones((1,), bool), (lo[1:] != lo[:-1]) | (hi[1:] != hi[:-1])]
    )
    return lo, hi, valid & first


@jax.jit
def _count_real(lo):
    return jnp.sum(lo != _SENTINEL)


@jax.jit
def _compact_slots(lo, hi, out_lo, out_hi):
    """Scatter the real slots to the front of a (cap,)-sized buffer.

    The tile programs emit mostly-sentinel slot arrays (one slot per tile
    cell); sorting those directly is O(total cells log cells) — compacting
    first makes the dedup sort run on ~m candidates instead.  ``out_lo`` /
    ``out_hi`` are sentinel-filled buffers whose size bounds the real count.
    """
    valid = lo != _SENTINEL
    dst = jnp.where(valid, jnp.cumsum(valid) - 1, out_lo.shape[0])
    return (
        out_lo.at[dst].set(lo, mode="drop"),
        out_hi.at[dst].set(hi, mode="drop"),
    )


@trace.span("rng.emit")
def sbcn_candidates(
    x: jax.Array,
    cd2_kmax: jax.Array,
    perm: np.ndarray,
    a_start: np.ndarray,
    a_len: np.ndarray,
    b_start: np.ndarray,
    b_len: np.ndarray,
    *,
    tile_elems: int = _TILE_ELEMS,
    pair_cap: int = _PAIR_ELEM_CAP,
    row_chunk: int = _ROW_CHUNK,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """All SBCN candidate edges across WSPD pairs, device-resident.

    The (start, len) pair ranges index the fair-split tree's ``perm`` array;
    all bucketing/padding is vectorized numpy control-plane work (no per-pair
    Python, no device sync).  Returns ``(lo, hi, keep)`` jax int32/bool
    arrays: padded candidate slots sorted by (lo, hi) with ``keep`` marking
    the unique real edges — downstream stages mask instead of compacting, so
    nothing crosses back to the host here.
    """
    perm = perm.astype(np.int64)

    # canonicalize |A| <= |B|
    swap = a_len > b_len
    a_start, b_start = np.where(swap, b_start, a_start), np.where(swap, a_start, b_start)
    a_len, b_len = np.where(swap, b_len, a_len), np.where(swap, a_len, b_len)

    los: list[jax.Array] = []
    his: list[jax.Array] = []

    # fast path: singleton-singleton pairs ARE their own SBCN edge
    ss = (a_len == 1) & (b_len == 1)
    if ss.any():
        pa = perm[a_start[ss]].astype(np.int32)
        pb = perm[b_start[ss]].astype(np.int32)
        los.append(jnp.asarray(np.minimum(pa, pb)))
        his.append(jnp.asarray(np.maximum(pa, pb)))

    rest = np.nonzero(~ss)[0]
    if len(rest):
        al, bl = a_len[rest], b_len[rest]
        # quantize pair sizes to pow2 tiers: with |A| <= |B| canonicalized
        # this is ~30 compiled tile programs, and padded tile area stays
        # within ~20% of the intrinsic sum(|A|*|B|) — coarser tiers (e.g.
        # {1,8,64,512}) compile fewer programs but inflate the slot arrays
        # (and every downstream compaction) by ~4x.
        tiers = np.array([1, 2, 4, 8, 16, 32, 64, 128, 256, 512], np.int64)

        def tier_of(v):
            return tiers[np.searchsorted(tiers, np.minimum(v, tiers[-1]))]

        ka = tier_of(al)
        kb = tier_of(bl)
        big = (al > tiers[-1]) | (bl > tiers[-1]) | (ka * kb > pair_cap)

        for key in np.unique(ka[~big] * (1 << 32) + kb[~big]):
            kaa, kbb = int(key >> 32), int(key & ((1 << 32) - 1))
            sel = rest[(ka == kaa) & (kb == kbb) & ~big]
            P = len(sel)
            # vectorized padded gather of pair point-sets
            ar = a_start[sel][:, None] + np.arange(kaa)[None, :]
            av = (np.arange(kaa)[None, :] < a_len[sel][:, None])
            a_pad = np.where(av, perm[np.minimum(ar, len(perm) - 1)], -1).astype(np.int32)
            br = b_start[sel][:, None] + np.arange(kbb)[None, :]
            bv = (np.arange(kbb)[None, :] < b_len[sel][:, None])
            b_pad = np.where(bv, perm[np.minimum(br, len(perm) - 1)], -1).astype(np.int32)

            # chunk shape: bounded by the tile budget AND by the tier's actual
            # pair count rounded to a power of two — padding a small tier up
            # to the full tile budget would burn orders of magnitude more
            # compute than the real pairs.  Pow2 rounding keeps the jit cache
            # at ~1 program per tier per dataset scale.
            chunk = max(1, min(tile_elems // (kaa * kbb), 1 << (P - 1).bit_length()))
            P_pad = -(-P // chunk) * chunk
            if P_pad != P:
                a_pad = np.concatenate([a_pad, np.full((P_pad - P, kaa), -1, np.int32)])
                b_pad = np.concatenate([b_pad, np.full((P_pad - P, kbb), -1, np.int32)])
            for c0 in range(0, P_pad, chunk):
                lo_c, hi_c = _sbcn_tier_chunk(
                    x,
                    cd2_kmax,
                    jnp.asarray(a_pad[c0 : c0 + chunk]),
                    jnp.asarray(b_pad[c0 : c0 + chunk]),
                )
                los.append(lo_c)
                his.append(hi_c)

        for gi in np.nonzero(big)[0]:
            sel = rest[gi]
            a = perm[a_start[sel] : a_start[sel] + a_len[sel]].astype(np.int32)
            b = perm[b_start[sel] : b_start[sel] + b_len[sel]].astype(np.int32)
            aj, bj = jnp.asarray(a), jnp.asarray(b)
            mutual = _sbcn_large(x, cd2_kmax, aj, bj, row_chunk=row_chunk)
            ga = jnp.broadcast_to(aj[:, None], mutual.shape)
            gb = jnp.broadcast_to(bj[None, :], mutual.shape)
            los.append(jnp.where(mutual, jnp.minimum(ga, gb), _SENTINEL).reshape(-1))
            his.append(jnp.where(mutual, jnp.maximum(ga, gb), _SENTINEL).reshape(-1))

    if not los:
        z = jnp.zeros((0,), jnp.int32)
        return z, z, jnp.zeros((0,), bool)
    lo_all = jnp.concatenate(los)
    hi_all = jnp.concatenate(his)
    # ONE scalar sync sizes the compaction buffer (the only host round-trip
    # in candidate generation); everything else stays device-resident.
    from .. import engine

    n_real = int(engine.to_host(_count_real(lo_all), "candidate_slots"))
    if n_real == 0:
        z = jnp.zeros((0,), jnp.int32)
        return z, z, jnp.zeros((0,), bool)
    cap = -(-n_real // 4096) * 4096  # quantized: reuses the sort/dedup programs
    buf = jnp.full((cap,), _SENTINEL, jnp.int32)
    lo_c, hi_c = _compact_slots(lo_all, hi_all, buf, buf)
    return _dedup_sorted(lo_c, hi_c)


# ---------------------------------------------------------------------------
# Fused-cascade emission: bounded per-row candidate keys (PR 3)
# ---------------------------------------------------------------------------
#
# The slot-array path above emits one slot per TILE CELL (|A|x|B| per pair —
# ~8M slots for ~1M candidates at n=4000) and pays for it downstream: a
# 2-array scatter compaction over every cell plus a variadic 2-key dedup
# sort.  The cascade path emits at most ``tie_cap`` packed int32 keys per
# (pair, A-row) — an SBCN edge must be its row's minimum, so ``tie_cap``
# bounds real emissions except under mass ties — and detects the tie
# overflow EXACTLY so the caller can fall back to the dense slot path
# (semantics preserved under heavy duplicates).  Keys pack (lo, hi) as
# ``lo * n + hi``; the single-key sort dedups ~7x faster than the variadic
# sort and doubles as the compaction (sentinels sort to the end).

_SMALL_AMAX = 4          # bucketed-tier path bounds (pow2-exact tiers)
_SMALL_BMAX = 8
_TIER_CHUNK_ELEMS = 1 << 17   # fixed cells per tier chunk => shape-stable programs
_ROWPATH_CELLS = 1 << 21      # padded cells per row-path launch (sizes its pair block)

# Compile contracts for the two emission families (enforced by
# engine.plan.cached_program at registration, re-derived statically by
# tools.audit.registry from the quantizer math above).  Small tiers walk the
# pow2-exact (kaa <= kbb) grid under (_SMALL_AMAX, _SMALL_BMAX) minus the
# host-emitted (1, 1) singleton pair: 8 shapes; chunk is a pure function of
# (kaa, kbb).  The row path walks (rc, nc, nb) pow2 ladders capped by the
# packed-key n <= 46340 gate; Pb is derived (``_rowpath_block``).  See
# tools/audit/registry.py for the exact enumerations these ceilings are
# checked against.
from .. import engine as _engine  # noqa: E402  (constants above feed the declarations)

_engine.plan.declare_family(
    "tier_emit",
    key_schema=("kaa", "kbb", "chunk", "tie_cap", "d"),
    bucket_dims=("kaa", "kbb", "chunk"),
    max_programs=8,
)
_engine.plan.declare_family(
    "rowpath_emit",
    key_schema=("rc", "nc", "nb", "pb", "tie_cap", "d"),
    bucket_dims=("rc", "nc", "nb", "pb"),
    max_programs=132,
)


def _pack_keys(lo, hi, n_pack, found):
    return jnp.where(found, lo * n_pack + hi, _SENTINEL)


def _emit_from_mask(mask, a_idx, b_idx, n_pack, tie_cap: int):
    """Per-row top-``tie_cap`` emission from an SBCN mutual mask.

    mask (P, A, B) bool; a_idx (P, A) / b_idx (P, B) int32 point ids (-1 pad).
    Returns (keys (P*A*tie_cap,), counters (2,)): packed candidate keys and
    [n_mutual_slots, n_rows_overflowing].  Selection keeps the first
    ``tie_cap`` set columns per row — identical to the dense mask whenever no
    row has more than ``tie_cap`` tied minima (counters[1] reports exactly
    when that fails, so callers can fall back without losing edges).
    """
    P, A, B = mask.shape
    if B <= max(tie_cap, 4):
        # narrow tiers: dense emission (every cell is a slot) costs at most
        # one extra slot per row and skips the selection passes entirely;
        # overflow is impossible because nothing is dropped
        lo = jnp.minimum(a_idx[:, :, None], b_idx[:, None, :])
        hi = jnp.maximum(a_idx[:, :, None], b_idx[:, None, :])
        keys = _pack_keys(lo, hi, n_pack, mask)
        counters = jnp.stack([jnp.sum(mask, dtype=jnp.int32), jnp.int32(0)])
        return keys.reshape(P * A * B), counters
    iota_b = jnp.arange(B, dtype=jnp.int32)
    m = mask
    keys = []
    for _ in range(min(tie_cap, B)):  # a row has at most B set columns
        j = jax.lax.argmax(m, 2, jnp.int32)                       # (P, A)
        found = jnp.take_along_axis(m, j[..., None], axis=2)[..., 0]
        gb = jnp.take_along_axis(b_idx, j, axis=1)                # (P, A)
        lo = jnp.minimum(a_idx, gb)
        hi = jnp.maximum(a_idx, gb)
        keys.append(_pack_keys(lo, hi, n_pack, found))
        m = m & (iota_b[None, None, :] != j[..., None])
    counts = jnp.sum(mask, axis=2, dtype=jnp.int32)
    counters = jnp.stack(
        [jnp.sum(counts, dtype=jnp.int32), jnp.sum(counts > tie_cap, dtype=jnp.int32)]
    )
    return jnp.stack(keys, axis=-1).reshape(P * A * len(keys)), counters


@functools.partial(jax.jit, static_argnames=("tie_cap",))
def _tier_emit(x, cd2k, a_idx, b_idx, n_pack, *, tie_cap: int):
    """One fixed-shape bucketed-tier chunk -> bounded packed keys + counters."""
    mutual = _mutual_mask(x, cd2k, a_idx, b_idx)
    return _emit_from_mask(mutual, a_idx, b_idx, n_pack, tie_cap)


def _rowpath_block(rc: int, nc: int, nb: int) -> int:
    """Pairs per row-path launch: as many as ``_ROWPATH_CELLS`` padded cells
    hold, at least 2.  A pure function of the shape key, so each shape is
    one program whatever the number of pairs."""
    return max(2, _ROWPATH_CELLS // (nc * rc * nb))


@functools.partial(jax.jit, static_argnames=("tie_cap",))
def _rowpath_emit(x, cd2k, a_chunks, b_idx, n_pack, *, tie_cap: int):
    """SBCN emission for a block of same-shape pairs too large for a tier.

    a_chunks (Pb, nc, rc) int32 padded -1; b_idx (Pb, nb) padded -1.  With
    one row chunk (nc == 1) the block is ONE batched (Pb, rc, nb) mrd tile,
    the tier path's ``_mutual_mask``.  With more, each pair in turn runs the
    two-pass row-chunked min-reduction of ``_sbcn_large`` (bit-identical mrd
    tiles and tie tolerance), so peak memory is O(rc * nb) per pair
    regardless of na.  Both emit bounded per-row keys, not the dense mask.
    """
    if a_chunks.shape[1] == 1:
        a_idx = a_chunks[:, 0]
        mutual = _mutual_mask(x, cd2k, a_idx, b_idx)
        return _emit_from_mask(mutual, a_idx, b_idx, n_pack, tie_cap)

    eps = jnp.float32(_EPS)

    def one_pair(args):
        ac_all, bj = args                                # (nc, rc), (nb,)
        xb = x[bj].astype(jnp.float32)
        cdb = cd2k[bj]
        bnorm = jnp.sum(xb * xb, -1)
        b_bad = bj < 0

        def mrd_chunk(ac):
            xa = x[ac].astype(jnp.float32)
            anorm = jnp.sum(xa * xa, -1)
            d2 = anorm[:, None] + bnorm[None, :] - 2.0 * xa @ xb.T
            m = jnp.maximum(
                jnp.maximum(cd2k[ac][:, None], cdb[None, :]), jnp.maximum(d2, 0.0)
            )
            m = jnp.where((ac < 0)[:, None] | b_bad[None, :], jnp.inf, m)
            tol = eps * (anorm[:, None] + bnorm[None, :])
            return m, tol

        def pass1(ac):
            return jnp.min(mrd_chunk(ac)[0], axis=0)

        col_min = jnp.min(jax.lax.map(pass1, ac_all), axis=0)[None, :]

        def pass2(ac):
            m, tol = mrd_chunk(ac)
            row_min = jnp.min(m, axis=1, keepdims=True)
            mask = (m <= row_min + tol) & (m <= col_min + tol) & jnp.isfinite(m)
            return _emit_from_mask(mask[None], ac[None], bj[None], n_pack, tie_cap)

        keys, counters = jax.lax.map(pass2, ac_all)
        return keys.reshape(-1), jnp.sum(counters, axis=0, dtype=jnp.int32)

    keys, counters = jax.lax.map(one_pair, (a_chunks, b_idx))
    return keys.reshape(-1), jnp.sum(counters, axis=0, dtype=jnp.int32)


@jax.jit
def _sort_dedup_stats(keys):
    """Sort packed keys (sentinels last); return (sorted, n_real, n_unique)."""
    ks = jnp.sort(keys)
    valid = ks != _SENTINEL
    first = jnp.concatenate([jnp.ones((1,), bool), ks[1:] != ks[:-1]])
    return ks, jnp.sum(valid, dtype=jnp.int32), jnp.sum(valid & first, dtype=jnp.int32)


def _pow2_ceil(v: int) -> int:
    return 1 << max(0, int(v - 1).bit_length())


def _pow2_ceil_np(v: np.ndarray) -> np.ndarray:
    """Vectorized pow2 round-up (exact: log2 of small ints is exact in f64)."""
    return np.left_shift(
        np.int64(1),
        np.ceil(np.log2(np.maximum(v, 1))).astype(np.int64),
    )


@trace.span("rng.emit")
def cascade_candidates(
    x: jax.Array,
    cd2_kmax: jax.Array,
    perm: np.ndarray,
    a_start: np.ndarray,
    a_len: np.ndarray,
    b_start: np.ndarray,
    b_len: np.ndarray,
    *,
    tie_cap: int = 2,
    tier_chunk_elems: int = _TIER_CHUNK_ELEMS,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """Bounded-emission SBCN candidates as sorted packed keys, device-resident.

    Returns device values ``(keys_sorted, n_real, n_unique, n_mutual,
    n_overflow)``.  ``keys_sorted`` is pow2-padded with sentinels;
    ``n_overflow > 0`` means some (pair, row) had more than ``tie_cap`` tied
    SBCN minima and the caller MUST fall back to ``sbcn_candidates`` (the
    dense slot path) — emission would otherwise drop tied edges.  No host
    sync happens here; the caller materializes the four scalars at its own
    ledger point.

    Requires n <= 46340 (packed ``lo * n + hi`` must fit int32); callers
    gate on that before choosing this path.

    The whole call is the ``rng.emit`` span; each emission launch counts as
    ``emit.tier_dispatches`` or ``emit.rowpath_dispatches``, and the host
    blocks it uploads as ``emit.upload_bytes`` (``engine.trace``).
    """
    from .. import engine

    perm = perm.astype(np.int32)  # halves the gather traffic below
    swap = a_len > b_len
    a_start, b_start = np.where(swap, b_start, a_start), np.where(swap, a_start, b_start)
    a_len, b_len = np.where(swap, b_len, a_len), np.where(swap, a_len, b_len)

    n_pack = jnp.int32(x.shape[0])
    key_parts: list[jax.Array] = []
    counter_parts: list[jax.Array] = []

    # singleton-singleton pairs ARE their own SBCN edge: emit on the host
    # control plane (pure numpy), zero device compute
    ss = (a_len == 1) & (b_len == 1)
    n_ss = int(ss.sum())
    if n_ss:
        pa = perm[a_start[ss]]
        pb = perm[b_start[ss]]
        ss_keys = (
            np.minimum(pa, pb).astype(np.int64) * int(x.shape[0])
            + np.maximum(pa, pb)
        )
        ss_keys = ss_keys.astype(np.int32)
        trace.count("emit.upload_bytes", ss_keys.nbytes)
        key_parts.append(jnp.asarray(ss_keys))

    rest = np.nonzero(~ss)[0]
    if len(rest):
        al, bl = a_len[rest], b_len[rest]
        small = (al <= _SMALL_AMAX) & (bl <= _SMALL_BMAX)

        # -- small tiers: pow2-exact (amax, bmax), FIXED chunk per tier ------
        ka = _pow2_ceil_np(al)
        kb = _pow2_ceil_np(bl)
        for key in np.unique(ka[small] * 16 + kb[small]) if small.any() else []:
            kaa, kbb = int(key) // 16, int(key) % 16
            sel = rest[small & (ka == kaa) & (kb == kbb)]
            P = len(sel)
            chunk = max(8, tier_chunk_elems // (kaa * kbb))
            P_pad = -(-P // chunk) * chunk
            a_pad = _padded_gather(perm, a_start[sel], a_len[sel], kaa, P_pad)
            b_pad = _padded_gather(perm, b_start[sel], b_len[sel], kbb, P_pad)
            emit = engine.plan.cached_program(
                ("tier_emit", kaa, kbb, chunk, tie_cap, x.shape[1]),
                lambda: functools.partial(_tier_emit, tie_cap=tie_cap),
            )
            for c0 in range(0, P_pad, chunk):
                a_blk, b_blk = a_pad[c0 : c0 + chunk], b_pad[c0 : c0 + chunk]
                trace.count("emit.tier_dispatches")
                trace.count("emit.upload_bytes", a_blk.nbytes + b_blk.nbytes)
                keys_c, counters_c = emit(
                    x, cd2_kmax, jnp.asarray(a_blk), jnp.asarray(b_blk), n_pack
                )
                key_parts.append(keys_c)
                counter_parts.append(counters_c)

        # -- row path: everything larger, grouped by padded shape -----------
        rp = rest[~small]
        if len(rp):
            na, nb = a_len[rp], b_len[rp]
            # pow2 ladders (min row chunk 32, min b width 64): a handful of
            # shape-stable programs, padded area within ~2x of intrinsic
            rc = np.minimum(256, np.maximum(32, _pow2_ceil_np(na)))
            nc = _pow2_ceil_np(-(-na // rc))
            nbp = np.maximum(64, _pow2_ceil_np(nb))
            shape_key = rc * (1 << 40) + nc * (1 << 20) + nbp
            for skey in np.unique(shape_key):
                sel = rp[shape_key == skey]
                rcc = int(rc[shape_key == skey][0])
                ncc = int(nc[shape_key == skey][0])
                nbb = int(nbp[shape_key == skey][0])
                # a launch holds as many pairs as the cell budget allows:
                # up to 1,024 of the smallest tiles, 2 of the largest
                Pb = _rowpath_block(rcc, ncc, nbb)
                emit = engine.plan.cached_program(
                    ("rowpath_emit", rcc, ncc, nbb, Pb, tie_cap, x.shape[1]),
                    lambda: functools.partial(_rowpath_emit, tie_cap=tie_cap),
                )
                for g0 in range(0, len(sel), Pb):
                    grp = sel[g0 : g0 + Pb]
                    a_blk = _padded_gather(
                        perm, a_start[grp], a_len[grp], ncc * rcc, Pb
                    ).reshape(Pb, ncc, rcc)
                    b_blk = _padded_gather(perm, b_start[grp], b_len[grp], nbb, Pb)
                    trace.count("emit.rowpath_dispatches")
                    trace.count("emit.upload_bytes", a_blk.nbytes + b_blk.nbytes)
                    keys_c, counters_c = emit(
                        x, cd2_kmax, jnp.asarray(a_blk), jnp.asarray(b_blk), n_pack
                    )
                    key_parts.append(keys_c)
                    counter_parts.append(counters_c)

    if not key_parts:
        z = jnp.full((8,), _SENTINEL, jnp.int32)
        zero = jnp.int32(0)
        return z, zero, zero, zero, zero

    keys = jnp.concatenate(key_parts)
    # quantize the sort length to coarse blocks: ~1 sort program per scale,
    # <=12.5% padding (a full pow2 round-up can nearly double the sort)
    q = 1 << 18
    total = min(_pow2_ceil(keys.shape[0]), -(-keys.shape[0] // q) * q)
    if total != keys.shape[0]:
        keys = jnp.concatenate(
            [keys, jnp.full((total - keys.shape[0],), _SENTINEL, jnp.int32)]
        )
    keys_sorted, n_real, n_unique = _sort_dedup_stats(keys)
    if counter_parts:
        counters = jnp.sum(jnp.stack(counter_parts), axis=0, dtype=jnp.int32)
    else:
        counters = jnp.zeros((2,), jnp.int32)
    n_mutual = counters[0] + jnp.int32(n_ss)
    return keys_sorted, n_real, n_unique, n_mutual, counters[1]


def _padded_gather(perm, starts, lens, width: int, rows: int):
    """(rows, width) int32 point-id matrix from (start, len) perm ranges,
    padded with -1 (short ranges AND missing rows)."""
    out = np.full((rows, width), -1, np.int32)
    k = len(starts)
    if k:
        r = starts[:, None] + np.arange(width)[None, :]
        v = np.arange(width)[None, :] < lens[:, None]
        out[:k] = np.where(v, perm[np.minimum(r, len(perm) - 1)], -1)
    return out


def sbcn_edges(
    x: jax.Array,
    cd2_kmax: jax.Array,
    perm: np.ndarray,
    a_start: np.ndarray,
    a_len: np.ndarray,
    b_start: np.ndarray,
    b_len: np.ndarray,
) -> np.ndarray:
    """Host-compacted SBCN edges: (m, 2) int64, a < b, unique.

    One materialization of the device candidate set (the pipeline proper
    stays on ``sbcn_candidates`` and defers this to the graph compaction).
    """
    from .. import engine

    lo, hi, keep = sbcn_candidates(
        x, cd2_kmax, perm, a_start, a_len, b_start, b_len
    )
    lo, hi, keep = engine.to_host((lo, hi, keep), "candidates")
    return np.stack([lo[keep].astype(np.int64), hi[keep].astype(np.int64)], axis=1)
