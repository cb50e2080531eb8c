"""Fused filter cascade: golden edge-set identity against the retained
slot-array path, Pallas-kernel vs jnp-twin parity, tie-overflow fallback,
the persistent program cache, and the row path's launch grouping."""

import dataclasses
import itertools

import jax.numpy as jnp
import numpy as np
import pytest

from repro import engine
from repro.core import mrd, sbcn, wspd
from repro.core import rng as rng_mod
from repro.kernels import fused_cascade, ops


def _moons(n_half=110, seed=3):
    rng = np.random.default_rng(seed)
    t = rng.uniform(0, np.pi, size=(n_half,))
    x = np.concatenate([
        np.stack([np.cos(t), np.sin(t)], 1),
        np.stack([1.0 - np.cos(t), 0.5 - np.sin(t)], 1),
    ]).astype(np.float32)
    return x + rng.normal(0, 0.06, size=x.shape).astype(np.float32)


def _anisotropic(n=220, seed=5):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, 2))
    x = x @ np.array([[0.6, -0.6], [-0.35, 0.85]])  # shear
    x[: n // 2] += (4.0, 0.0)
    return x.astype(np.float32)


def _datasets(blobs):
    return {
        "blobs": blobs[0],
        "moons": _moons(),
        "anisotropic": _anisotropic(),
    }


@pytest.mark.parametrize("variant", ["rng_star", "rng"])
def test_fused_matches_slot_path_golden(blobs, variant):
    """Golden: the fused cascade's edge set must be IDENTICAL (values and
    order) to the retained slot-array path on every test dataset — not just
    label-identical."""
    plan = engine.resolve_plan("auto")
    plan_ref = dataclasses.replace(plan, backend="ref")  # forces the slot path
    for name, x in _datasets(blobs).items():
        xj = jnp.asarray(x)
        knn_d2, knn_idx = ops.knn(xj, 9)
        fused = rng_mod.build_rng_graph(xj, knn_d2, knn_idx, variant=variant, plan=plan)
        slot = rng_mod.build_rng_graph(
            xj, knn_d2, knn_idx, variant=variant, plan=plan_ref
        )
        assert fused.stats.get("path") == "fused", (name, fused.stats)
        assert "path" not in slot.stats
        np.testing.assert_array_equal(fused.edges, slot.edges, err_msg=name)
        # weights may differ by ulps (same diff-form formula, different
        # compiled programs); the EDGE SET is the bit-exact contract
        np.testing.assert_allclose(fused.d2, slot.d2, rtol=2e-7, err_msg=name)
        np.testing.assert_allclose(
            fused.w2_kmax, slot.w2_kmax, rtol=2e-7, err_msg=name
        )


def test_edge_cascade_pallas_interpret_matches_jnp(blobs):
    """The Pallas kernel (interpret mode) and the jnp twin are the same
    program family: identical verdicts, certificates, and float outputs."""
    x, _ = blobs
    xj = jnp.asarray(x)
    k = 7
    knn_d2, knn_idx = ops.knn(xj, k)
    cd2k = knn_d2[:, -1]
    rng = np.random.default_rng(0)
    m = 513  # deliberately not a tile multiple
    ea = jnp.asarray(rng.integers(0, len(x), m).astype(np.int32))
    eb = jnp.asarray((np.asarray(ea) + 1 + rng.integers(0, len(x) - 1, m)) % len(x)).astype(jnp.int32)
    valid = jnp.asarray(rng.random(m) > 0.1)
    # chunk 128: several kernel calls, the last one ragged
    for k_check, chunk in itertools.product((2, k), (65536, 128)):
        out_j = fused_cascade.edge_cascade(
            xj, cd2k, knn_idx, knn_d2, ea, eb, valid,
            k_check=k_check, backend="jnp",
        )
        out_p = fused_cascade.edge_cascade(
            xj, cd2k, knn_idx, knn_d2, ea, eb, valid,
            k_check=k_check, backend="pallas_interpret", chunk=chunk,
        )
        vj = np.asarray(valid)
        np.testing.assert_array_equal(np.asarray(out_j[0]), np.asarray(out_p[0]))
        np.testing.assert_array_equal(np.asarray(out_j[1]), np.asarray(out_p[1]))
        np.testing.assert_allclose(
            np.asarray(out_j[2])[vj], np.asarray(out_p[2])[vj], rtol=1e-6, atol=1e-7
        )
        np.testing.assert_allclose(
            np.asarray(out_j[3])[vj], np.asarray(out_p[3])[vj], rtol=1e-6, atol=1e-7
        )


def test_staged_equals_unstaged_verdict(blobs):
    """Stage-1 removals are a strict subset of the full check's: staging can
    never change the final verdict (the exactness argument behind the fused
    pipeline)."""
    x, _ = blobs
    xj = jnp.asarray(x)
    knn_d2, knn_idx = ops.knn(xj, 9)
    cd2k = knn_d2[:, -1]
    rng = np.random.default_rng(1)
    m = 400
    ea = jnp.asarray(rng.integers(0, len(x), m).astype(np.int32))
    eb = jnp.asarray((np.asarray(ea) + 1 + rng.integers(0, len(x) - 1, m)) % len(x)).astype(jnp.int32)
    valid = jnp.ones((m,), bool)
    killed1 = fused_cascade.edge_cascade(
        xj, cd2k, knn_idx, knn_d2, ea, eb, valid, k_check=2, backend="jnp"
    )[0]
    killed_full = fused_cascade.edge_cascade(
        xj, cd2k, knn_idx, knn_d2, ea, eb, valid, k_check=9, backend="jnp"
    )[0]
    k1, kf = np.asarray(killed1), np.asarray(killed_full)
    assert (~kf[k1]).sum() == 0  # stage-1 kills are a subset of full kills
    assert kf.sum() > k1.sum() > 0  # and staging actually prunes something


def test_tie_overflow_falls_back_to_slot_path():
    """Mass-duplicated points overflow the bounded per-row emission; the
    build must detect that EXACTLY and fall back to the dense slot path,
    producing the identical graph the ref backend computes."""
    rng = np.random.default_rng(7)
    base = rng.normal(size=(40, 2)).astype(np.float32)
    x = np.repeat(base, 8, axis=0)  # every point duplicated 8x
    xj = jnp.asarray(x)
    knn_d2, knn_idx = ops.knn(xj, 7)
    plan = engine.resolve_plan("auto")
    g = rng_mod.build_rng_graph(xj, knn_d2, knn_idx, variant="rng_star", plan=plan)
    assert g.stats.get("path") != "fused"  # overflow forced the fallback
    g_ref = rng_mod.build_rng_graph(
        xj, knn_d2, knn_idx, variant="rng_star",
        plan=dataclasses.replace(plan, backend="ref"),
    )
    np.testing.assert_array_equal(g.edges, g_ref.edges)


def test_program_cache_persists_across_plans(blobs):
    """Two Plan instances over the same data shape share cached programs."""
    x, _ = blobs
    xj = jnp.asarray(x)
    knn_d2, knn_idx = ops.knn(xj, 7)
    p1 = engine.resolve_plan("auto")
    p2 = engine.resolve_plan("auto")
    assert p1 is not p2
    rng_mod.build_rng_graph(xj, knn_d2, knn_idx, variant="rng_star", plan=p1)
    before = set(engine.plan.program_cache_info())
    assert any(k[0] in ("tier_emit", "rowpath_emit") for k in before)
    rng_mod.build_rng_graph(xj, knn_d2, knn_idx, variant="rng_star", plan=p2)
    assert set(engine.plan.program_cache_info()) == before  # no new builds


def test_fused_pack_limit_falls_back():
    """n beyond the int32 packing limit must route to the slot path."""
    assert rng_mod._PACK_LIMIT ** 2 + rng_mod._PACK_LIMIT < 2 ** 31
    rng = np.random.default_rng(2)
    x = rng.normal(size=(60, 2)).astype(np.float32)
    xj = jnp.asarray(x)
    knn_d2, knn_idx = ops.knn(xj, 5)
    plan = engine.resolve_plan("auto")
    import unittest.mock as mock

    with mock.patch.object(rng_mod, "_PACK_LIMIT", 10):
        g = rng_mod.build_rng_graph(xj, knn_d2, knn_idx, variant="rng_star", plan=plan)
    assert g.stats.get("path") != "fused"
    g2 = rng_mod.build_rng_graph(xj, knn_d2, knn_idx, variant="rng_star", plan=plan)
    assert g2.stats.get("path") == "fused"
    np.testing.assert_array_equal(g.edges, g2.edges)


# -- row path: launch grouping against a serial per-pair reference ----------


def _integer_normal(rng, size, offset=0.0):
    """Integer points under 2**11 in magnitude: every float32 product and sum
    of the mrd formula is exact, so a numpy reference rounds as XLA does."""
    return np.round(rng.normal(scale=50.0, size=size)) + offset


def _two_far_blobs(seed=0):
    """Two 300-point blobs far apart: the pair between them is row-chunked
    (nc 2), the pairs inside them fill two single-chunk row-path shapes."""
    rng = np.random.default_rng(seed)
    return np.concatenate(
        [_integer_normal(rng, (300, 2)), _integer_normal(rng, (300, 2), 1200.0)]
    ).astype(np.float32)


def _wspd(x, k=7):
    xj = jnp.asarray(x)
    knn_d2, _ = ops.knn(xj, k)
    cd2k = np.asarray(mrd.core_distances2(knn_d2)[:, -1])
    tree = wspd.build_fair_split_tree(
        np.asarray(x, np.float64), np.sqrt(cd2k.astype(np.float64))
    )
    pu, pv = wspd.wspd_pairs(tree)
    pairs = (
        tree.start[pu], tree.end[pu] - tree.start[pu],
        tree.start[pv], tree.end[pv] - tree.start[pv],
    )
    return cd2k, tree.perm, pairs


def _serial_reference(x, cd2k, perm, pairs, tie_cap):
    """One pair at a time in numpy float32, the dense SBCN mask of each, of
    which a row keeps its first ``tie_cap`` cells: (sorted unique packed
    keys, mutual cells, rows with more than ``tie_cap``)."""
    n = len(x)
    eps = np.float32(sbcn._EPS)
    keys, n_mutual, n_overflow = [], 0, 0
    for sa, la, sb, lb in zip(*pairs):
        ia, ib = perm[sa : sa + la], perm[sb : sb + lb]
        if la > lb:
            ia, ib = ib, ia  # rows are the smaller side, as emitted
        xa, xb = x[ia], x[ib]
        an, bn = np.sum(xa * xa, -1), np.sum(xb * xb, -1)
        d2 = np.maximum(an[:, None] + bn[None, :] - np.float32(2.0) * (xa @ xb.T), 0)
        m = np.maximum(np.maximum(cd2k[ia][:, None], cd2k[ib][None, :]), d2)
        tol = eps * (an[:, None] + bn[None, :])
        mask = (m <= m.min(1, keepdims=True) + tol) & (m <= m.min(0, keepdims=True) + tol)
        n_mutual += int(mask.sum())
        if mask.shape[1] > max(tie_cap, 4):  # narrower rows emit densely
            n_overflow += int((mask.sum(1) > tie_cap).sum())
            mask &= np.cumsum(mask, axis=1) <= tie_cap
        r, c = np.nonzero(mask)
        lo, hi = np.minimum(ia[r], ib[c]), np.maximum(ia[r], ib[c])
        keys.append(lo.astype(np.int64) * n + hi)
    return np.unique(np.concatenate(keys)), n_mutual, n_overflow


def _rowpath_groups(pairs):
    """{(rc, nc, nb): pair count} of the row path, by the documented rule."""
    al, bl = pairs[1], pairs[3]
    a, b = np.minimum(al, bl), np.maximum(al, bl)
    big = ~((a <= sbcn._SMALL_AMAX) & (b <= sbcn._SMALL_BMAX))
    groups = {}
    for na, nb in zip(a[big], b[big]):
        rc = min(256, max(32, sbcn._pow2_ceil(int(na))))
        key = (rc, sbcn._pow2_ceil(-(-int(na) // rc)), max(64, sbcn._pow2_ceil(int(nb))))
        groups[key] = groups.get(key, 0) + 1
    return groups


def _cascade(x, cd2k, perm, pairs):
    """``cascade_candidates`` as the fused build calls it, and its counters."""
    plan = engine.resolve_plan("auto")
    with engine.trace.recording() as rec:
        ks, n_real, n_unique, n_mutual, n_overflow = sbcn.cascade_candidates(
            jnp.asarray(x), jnp.asarray(cd2k), perm, *pairs,
            tie_cap=plan.cascade_tie_cap, tier_chunk_elems=plan.tier_chunk_elems,
        )
    ks = np.asarray(ks)[: int(n_real)]
    return np.unique(ks), int(n_unique), int(n_mutual), int(n_overflow), rec.counters()


_DEFAULT_ROWPATH_CELLS = sbcn._ROWPATH_CELLS


@pytest.mark.parametrize("cells", [_DEFAULT_ROWPATH_CELLS, 1 << 14])
def test_rowpath_launches_match_serial_reference(cells, monkeypatch):
    """Whatever the launch size, the batched row path emits the serial
    per-pair reference's keys and counts, in one launch per started block
    of ``max(2, cells // (nc * rc * nb))`` pairs of each shape."""
    monkeypatch.setattr(sbcn, "_ROWPATH_CELLS", cells)
    x = _two_far_blobs()
    cd2k, perm, pairs = _wspd(x)
    groups = _rowpath_groups(pairs)
    assert len(groups) >= 3 and any(nc > 1 for _, nc, _ in groups), groups

    keys, n_unique, n_mutual, n_overflow, counters = _cascade(x, cd2k, perm, pairs)
    ref_keys, ref_mutual, ref_overflow = _serial_reference(
        x, cd2k, perm, pairs, engine.resolve_plan("auto").cascade_tie_cap
    )
    np.testing.assert_array_equal(keys, ref_keys)
    assert n_unique == len(ref_keys)
    assert (n_mutual, n_overflow) == (ref_mutual, ref_overflow)
    launches = sum(
        -(-p // max(2, cells // (nc * rc * nb))) for (rc, nc, nb), p in groups.items()
    )
    assert counters["emit.rowpath_dispatches"] == launches
    if cells == _DEFAULT_ROWPATH_CELLS:
        assert launches < sum(groups.values()) // 32  # blocks of far more than 32


def _tied_pairs(na, nb, seed, copies):
    """One row-path pair (A: na points, B: nb points, each B point ``copies``
    times): every row's minimum is a ``copies``-way tie."""
    rng = np.random.default_rng(seed)
    xa = _integer_normal(rng, (na, 2))
    xb = _integer_normal(rng, (-(-nb // copies), 2), 300.0)
    xb = np.repeat(xb, copies, axis=0)[:nb]
    x = np.concatenate([xa, xb]).astype(np.float32)
    knn_d2, _ = ops.knn(jnp.asarray(x), 4)
    cd2k = np.asarray(knn_d2[:, 0])  # nearest neighbour: 0 for every B copy
    pair = tuple(np.array([v], np.int64) for v in (0, na, na, nb))
    return x, cd2k, np.arange(len(x)), pair


@pytest.mark.parametrize("na,nb", [(40, 99), (300, 540)])
def test_rowpath_tie_overflow_counted_exactly(na, nb):
    """A batched tile (nc 1) and a row-chunked pair (nc 2) both report the
    reference's exact count of rows with more tied minima than tie_cap."""
    tie_cap = engine.resolve_plan("auto").cascade_tie_cap
    x, cd2k, perm, pairs = _tied_pairs(na, nb, seed=na, copies=tie_cap + 1)
    _, _, n_mutual, n_overflow, counters = _cascade(x, cd2k, perm, pairs)
    _, ref_mutual, ref_overflow = _serial_reference(x, cd2k, perm, pairs, tie_cap)
    assert counters["emit.rowpath_dispatches"] == 1
    assert n_overflow == ref_overflow > 0
    assert n_mutual == ref_mutual


def test_rowpath_tie_overflow_falls_back_to_slot_path():
    """Duplicates inside two large blobs overflow row-path pairs: the build
    falls back and gives the ref backend's graph."""
    base = _two_far_blobs(seed=4)[::3]
    x = np.repeat(base, 3, axis=0)  # every point three times
    cd2k, perm, pairs = _wspd(x)
    rp = np.nonzero(
        ~((np.minimum(pairs[1], pairs[3]) <= sbcn._SMALL_AMAX)
          & (np.maximum(pairs[1], pairs[3]) <= sbcn._SMALL_BMAX))
    )[0]
    row_pairs = tuple(p[rp] for p in pairs)
    assert _cascade(x, cd2k, perm, row_pairs)[3] > 0  # the row path overflows

    xj = jnp.asarray(x)
    knn_d2, knn_idx = ops.knn(xj, 7)
    plan = engine.resolve_plan("auto")
    g = rng_mod.build_rng_graph(xj, knn_d2, knn_idx, variant="rng_star", plan=plan)
    assert g.stats.get("path") != "fused"
    g_ref = rng_mod.build_rng_graph(
        xj, knn_d2, knn_idx, variant="rng_star",
        plan=dataclasses.replace(plan, backend="ref"),
    )
    np.testing.assert_array_equal(g.edges, g_ref.edges)
