"""Plain HDBSCAN* reference: the answers the benchmark holds the program to.

Written from the published definitions (Campello, Moulavi and Sander 2013;
McInnes and Healy's ``hdbscan`` library for condensing, excess-of-mass
selection, membership strength and ``approximate_predict``).  It imports
nothing of the system under test and takes nothing it made: every core
distance, MST, tree and label here is computed from the data alone.

Conventions, as the system documents them for its users:

* core distance ``c_m(p)`` is the distance to p's (m-1)-th nearest *other*
  point (the m-NN includes p itself), so ``c_1 = 0``;
* mutual reachability ``mrd_m(a, b) = max(d(a, b), c_m(a), c_m(b))``;
* condensing uses ``min_cluster_size = max(2, mpts)``, selection is
  excess-of-mass without the root (``allow_single_cluster=False``);
* a query attaches through the fitted point, among its ``kmax-1`` nearest,
  with the least mutual reachability (ties to the nearer point) and climbs
  from the cluster that point leaves to the first cluster alive at its
  density.

Device work (distances, kNN, dense Prim over the complete graph) runs in
JAX with explicit differences, never a matrix product, in the precision
given: ``float32`` is the reference, ``bfloat16`` the control.  The tree
work is float64 numpy on the host.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

DTYPES = ("float32", "bfloat16")


def _jnp_dtype(precision: str):
    if precision not in DTYPES:
        raise ValueError(f"precision must be one of {DTYPES}; got {precision!r}")
    return jnp.float32 if precision == "float32" else jnp.bfloat16


# ---------------------------------------------------------------------------
# Device part: core distances and dense Prim
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("k", "block", "precision", "exclude_self"))
def _knn_d2(xq, x, *, k: int, block: int, precision: str, exclude_self: bool = False):
    """k smallest squared distances from each row of xq to x, ascending."""
    dt = _jnp_dtype(precision)
    q = xq.shape[0]
    q_pad = -(-q // block) * block
    xq_p = jnp.zeros((q_pad, xq.shape[1]), xq.dtype).at[:q].set(xq).astype(dt)
    xd = x.astype(dt)
    cols = jnp.arange(x.shape[0])

    def one(b):
        rows = jax.lax.dynamic_slice_in_dim(xq_p, b * block, block)
        diff = rows[:, None, :] - xd[None, :, :]
        d2 = jnp.sum(diff * diff, axis=-1).astype(jnp.float32)
        if exclude_self:
            ids = b * block + jnp.arange(block)
            d2 = jnp.where(ids[:, None] == cols[None, :], jnp.inf, d2)
        neg, idx = jax.lax.top_k(-d2, k)
        return -neg, idx

    d2, idx = jax.lax.map(one, jnp.arange(q_pad // block))
    return d2.reshape(q_pad, k)[:q], idx.reshape(q_pad, k)[:q]


def core_d2(x: np.ndarray, kmax: int, precision: str = "float32", block: int = 256):
    """(n, kmax) squared core distances; column m-1 holds c_m^2 (c_1 = 0)."""
    d2, _ = _knn_d2(jnp.asarray(x), jnp.asarray(x), k=kmax - 1,
                    block=min(block, len(x)), precision=precision, exclude_self=True)
    d2 = np.asarray(d2, np.float64)
    return np.concatenate([np.zeros((len(x), 1)), d2], axis=1)


@functools.partial(jax.jit, static_argnames=("precision",))
def _prim_rows(x, cd2_rows, *, precision: str):
    """Dense Prim over the complete graph, one MST per row of cd2_rows.

    Returns (parent, child, w2), each (R, n-1): the edge that added each
    vertex, in the order Prim added them.
    """
    dt = _jnp_dtype(precision)
    xd = x.astype(dt)
    R, n = cd2_rows.shape
    rows = jnp.arange(R)
    cd = cd2_rows.astype(dt).astype(jnp.float32)

    def mrd_from(v):  # (R,) vertex per row -> (R, n) squared mrd
        diff = xd[v][:, None, :] - xd[None, :, :]
        d2 = jnp.sum(diff * diff, axis=-1).astype(jnp.float32)
        return jnp.maximum(jnp.maximum(d2, cd[rows, v][:, None]), cd)

    v0 = jnp.zeros((R,), jnp.int32)
    in_tree = jnp.zeros((R, n), bool).at[:, 0].set(True)
    best = mrd_from(v0)
    parent = jnp.zeros((R, n), jnp.int32)
    out = jnp.zeros((3, R, n - 1), jnp.float32)

    def body(i, st):
        in_tree, best, parent, out = st
        masked = jnp.where(in_tree, jnp.inf, best)
        v = jnp.argmin(masked, axis=1).astype(jnp.int32)
        w = masked[rows, v]
        out = out.at[:, :, i].set(
            jnp.stack([parent[rows, v].astype(jnp.float32), v.astype(jnp.float32), w]))
        in_tree = in_tree.at[rows, v].set(True)
        m = mrd_from(v)
        upd = m < best
        return (in_tree, jnp.where(upd, m, best),
                jnp.where(upd, v[:, None], parent), out)

    *_, out = jax.lax.fori_loop(0, n - 1, body, (in_tree, best, parent, out))
    return out


def dense_msts(x: np.ndarray, cd2: np.ndarray, mpts_values, precision: str = "float32"):
    """Exact mutual-reachability MSTs: list of (a, b, w) per mpts, w real."""
    if len(x) > (1 << 24):
        raise ValueError("vertex ids are carried in float32; n must be <= 2^24")
    rows = np.asarray([cd2[:, m - 1] for m in mpts_values], np.float32)
    out = np.asarray(_prim_rows(jnp.asarray(x), jnp.asarray(rows), precision=precision))
    res = []
    for r in range(len(mpts_values)):
        a = out[0, r].astype(np.int64)
        b = out[1, r].astype(np.int64)
        res.append((a, b, np.sqrt(out[2, r].astype(np.float64))))
    return res


# ---------------------------------------------------------------------------
# Host part: single linkage, condensed tree, excess of mass, labels
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Tree:
    """One condensed tree with its selection, for labels and prediction."""

    n: int
    point_cluster: np.ndarray   # (n,) cluster each point leaves
    point_lambda: np.ndarray    # (n,) lambda at which it leaves
    cluster_parent: np.ndarray  # (C,) parent cluster (root: -1); root is 0
    cluster_birth: np.ndarray   # (C,) lambda at which the cluster is born
    labels: np.ndarray          # (n,) label per point, -1 = noise
    cluster_label: np.ndarray   # (C,) label of the selected ancestor-or-self, -1
    label_max_lambda: np.ndarray  # (L,) deepest finite leaving lambda per label

    @property
    def probabilities(self) -> np.ndarray:
        return _strength(self.point_lambda, self.labels, self.label_max_lambda)


def _strength(lam, labels, max_lam):
    out = np.zeros(len(labels))
    m = labels >= 0
    if m.any():
        cap = max_lam[labels[m]]
        with np.errstate(invalid="ignore", divide="ignore"):
            out[m] = np.where(cap > 0, np.clip(lam[m] / cap, 0.0, 1.0), 1.0)
    return out


def _single_linkage(a, b, w, n):
    """Merges of a spanning tree in weight order: (left, right, dist, size)."""
    order = np.argsort(w, kind="stable")
    parent = list(range(n))
    node = list(range(n))      # dendrogram node of each union-find root
    size = [1] * (2 * n - 1)
    left = np.empty(n - 1, np.int64)
    right = np.empty(n - 1, np.int64)

    def find(v):
        r = v
        while parent[r] != r:
            r = parent[r]
        while parent[v] != r:
            parent[v], v = r, parent[v]
        return r

    for i, e in enumerate(order):
        ra, rb = find(int(a[e])), find(int(b[e]))
        if ra == rb:
            raise ValueError("the edge list is not a spanning tree")
        left[i], right[i] = node[ra], node[rb]
        size[n + i] = size[node[ra]] + size[node[rb]]
        parent[ra] = rb
        node[rb] = n + i
    return left, right, np.asarray(w, np.float64)[order], np.asarray(size)


def build_tree(a, b, w, n: int, mpts: int) -> Tree:
    """HDBSCAN* extraction at one mpts from its MST edges (real weights)."""
    mcs = max(2, mpts)
    left, right, dist, size = _single_linkage(a, b, w, n)
    with np.errstate(divide="ignore"):
        lam_of = np.where(dist > 0, 1.0 / dist, np.inf)

    point_cluster = np.zeros(n, np.int64)
    point_lambda = np.zeros(n)
    c_parent = [-1]
    c_birth = [0.0]
    # top-down over the dendrogram: (node, condensed cluster it belongs to)
    stack = [(2 * n - 2, 0)]
    while stack:
        node, c = stack.pop()
        i = node - n
        lam = lam_of[i]
        kids = (int(left[i]), int(right[i]))
        big = [size[k] >= mcs for k in kids]
        if big[0] and big[1]:
            for k in kids:
                c_parent.append(c)
                c_birth.append(lam)
                stack.append((k, len(c_parent) - 1))
            continue
        for k, is_big in zip(kids, big):
            if is_big:
                stack.append((k, c))
                continue
            todo = [k]          # every point under k leaves c at lam
            while todo:
                v = todo.pop()
                if v < n:
                    point_cluster[v] = c
                    point_lambda[v] = lam
                else:
                    todo.extend((int(left[v - n]), int(right[v - n])))

    C = len(c_parent)
    c_parent = np.asarray(c_parent, np.int64)
    c_birth = np.asarray(c_birth)

    finite = np.isfinite(point_lambda)
    cap = max(float(point_lambda[finite].max(initial=0.0)),
              float(c_birth[np.isfinite(c_birth)].max(initial=0.0)))
    cap = cap if cap > 0 else 1.0
    eff = lambda v: np.where(np.isfinite(v), v, cap)  # noqa: E731
    stab = np.zeros(C)
    np.add.at(stab, point_cluster, eff(point_lambda) - c_birth[point_cluster])
    kids_of = [[] for _ in range(C)]
    csize = np.zeros(C, np.int64)
    np.add.at(csize, point_cluster, 1)
    for c in range(C - 1, 0, -1):  # children are numbered after parents
        kids_of[c_parent[c]].append(c)
        csize[c_parent[c]] += csize[c]
    for c in range(1, C):
        stab[c_parent[c]] += (eff(c_birth[c]) - c_birth[c_parent[c]]) * csize[c]

    # excess of mass, bottom-up; the root is never selected
    selected = np.zeros(C, bool)
    best = stab.copy()
    for c in range(C - 1, -1, -1):
        if not kids_of[c]:
            selected[c] = c != 0
            continue
        s = sum(best[k] for k in kids_of[c])
        if c == 0 or s > stab[c]:
            best[c] = s
        else:
            selected[c] = True
            todo = list(kids_of[c])
            while todo:
                k = todo.pop()
                selected[k] = False
                todo.extend(kids_of[k])

    order = np.flatnonzero(selected)
    rank = np.full(C, -1, np.int64)
    rank[order] = np.arange(len(order))
    cluster_label = np.full(C, -1, np.int64)
    for c in range(C):  # parents first
        cluster_label[c] = rank[c] if selected[c] else (
            cluster_label[c_parent[c]] if c else -1)
    labels = cluster_label[point_cluster]
    point_lambda = np.where(labels >= 0, point_lambda, 0.0)
    max_lam = np.zeros(max(len(order), 1))
    m = (labels >= 0) & np.isfinite(point_lambda)
    np.maximum.at(max_lam, labels[m], point_lambda[m])
    return Tree(n, point_cluster, point_lambda, c_parent, c_birth, labels,
                cluster_label, max_lam)


def fit(x: np.ndarray, kmax: int, mpts_values, precision: str = "float32"):
    """Reference fit: (sorted MST weights (R, n-1), trees, cd2)."""
    cd2 = core_d2(x, kmax, precision)
    msts = dense_msts(x, cd2, mpts_values, precision)
    weights = np.stack([np.sort(w) for _, _, w in msts])
    trees = [build_tree(a, b, w, len(x), m) for (a, b, w), m in zip(msts, mpts_values)]
    return weights, trees, cd2


# ---------------------------------------------------------------------------
# Out-of-sample prediction
# ---------------------------------------------------------------------------


def predict(q: np.ndarray, x: np.ndarray, cd2: np.ndarray, tree: Tree, mpts: int,
            precision: str = "float32", block: int = 256):
    """(labels, probabilities) of query rows at one mpts, from ``tree``."""
    kq = cd2.shape[1] - 1
    qd2, qidx = _knn_d2(jnp.asarray(q), jnp.asarray(x), k=kq,
                        block=min(block, len(q)), precision=precision)
    qd2 = np.asarray(qd2, np.float64)
    qidx = np.asarray(qidx, np.int64)
    qc = np.zeros(len(q)) if mpts == 1 else qd2[:, mpts - 2]
    mrd = np.maximum(np.maximum(qd2, qc[:, None]), cd2[qidx, mpts - 1])
    j = np.argmin(mrd, axis=1)
    best = mrd[np.arange(len(q)), j]
    nbr = qidx[np.arange(len(q)), j]
    with np.errstate(divide="ignore"):
        lam = np.where(best > 0, 1.0 / np.sqrt(best), np.inf)
    c = tree.point_cluster[nbr]
    while True:
        move = (tree.cluster_birth[c] > lam) & (c != 0)
        if not move.any():
            break
        c = np.where(move, tree.cluster_parent[c], c)
    labels = tree.cluster_label[c]
    return labels, _strength(lam, labels, tree.label_max_lambda)


# ---------------------------------------------------------------------------
# Comparisons
# ---------------------------------------------------------------------------


def weight_gap(got_sorted: np.ndarray, want_sorted: np.ndarray) -> float:
    """Widest relative gap between two sorted MST weight multisets."""
    got = np.asarray(got_sorted, np.float64)
    want = np.asarray(want_sorted, np.float64)
    if got.shape != want.shape:
        return float("inf")
    scale = np.maximum(np.abs(want), np.finfo(np.float32).tiny)
    gap = np.abs(got - want) / scale
    return float(np.max(gap)) if gap.size else 0.0


def label_map(got: np.ndarray, want: np.ndarray) -> dict[int, int]:
    """Each label of ``got`` mapped to the ``want`` label it overlaps most."""
    out = {-1: -1}
    if np.shape(got) != np.shape(want):
        return out
    m = got >= 0
    for g in np.unique(got[m]):
        w, c = np.unique(want[got == g], return_counts=True)
        out[int(g)] = int(w[np.argmax(c)])
    return out


def answer_gap(got_labels, got_probs, want_labels, want_probs, mapping) -> float:
    """Widest gap between two sets of (label, membership) answers.

    ``mapping`` renames the labels of ``got`` into those of ``want`` (the
    fitted labellings' overlap).  A point whose label differs counts 1,
    the most two memberships can differ; elsewhere the gap is that of the
    membership probabilities.
    """
    got_labels = np.asarray(got_labels)
    if got_labels.shape != np.shape(want_labels):
        return float("inf")
    if not got_labels.size:
        return 0.0
    mapped = np.asarray([mapping.get(int(v), -2) for v in got_labels])
    gap = np.where(mapped == want_labels,
                   np.abs(np.asarray(got_probs, np.float64) - want_probs), 1.0)
    return float(np.max(gap))
