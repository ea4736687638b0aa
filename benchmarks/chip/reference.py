"""The plain reference that decides ``correct``.

It imports nothing of the program under test and takes nothing the
program made except the answers it checks.  Distances are recomputed from
the generated table: in float64 on the host for small sets, and in blocks
on the device for the full tables, in the direct form (euclidean) or at
``Precision.HIGHEST`` (cosine).

The numbers it returns, each compared with a limit kept in the cell's
workload file:

  order_gap    an exact VAT order is a Prim traversal: at every step the
               vertex taken has the least distance to the vertices taken
               before it, and the first vertex holds the largest
               dissimilarity.  The largest shortfall from either rule,
               over the mean admitting edge.
  order_bound  the approx rung's order is a traversal of its kNN spanning
               tree, so the distances that admit each vertex sum to at
               most the tree weight the fit reports: (sum - weight) /
               weight.
  image_err    the rendered image against the same image from float64
               distances: largest absolute difference over the largest
               reference entry (VAT image, band image, iVAT image).
  order_excess the approx order's admitting distances against the exact
               minimum spanning tree weight: (sum - exact) / exact, the
               approximation error of the kNN graph.
Exact checks (limit 0) ride along: the order is a permutation, the band
representatives sit at the band middles, the report's k_est lies in the
range the reference image's threshold allows, its Hopkins statistic in
(0, 1), and ``clustered`` follows its rule.
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

#: Relative room on the block-score threshold within which k_est may
#: legitimately fall on either side (a super-diagonal entry that close to
#: the threshold is decided by float32 rounding, not by the answer).
THRESHOLD_RTOL = 1e-4


# ----------------------------------------------------------- host, f64 ----

def dist64(A: np.ndarray, B: np.ndarray, metric: str) -> np.ndarray:
    """Pairwise dissimilarities in float64 (euclidean or cosine)."""
    A = np.asarray(A, np.float64)
    B = np.asarray(B, np.float64)
    if metric == "euclidean":
        sq = ((A * A).sum(1)[:, None] + (B * B).sum(1)[None, :]
              - 2.0 * A @ B.T)
        return np.sqrt(np.maximum(sq, 0.0))
    if metric == "cosine":
        na = np.linalg.norm(A, axis=1)
        nb = np.linalg.norm(B, axis=1)
        return np.clip(1.0 - (A @ B.T) / np.outer(na, nb), 0.0, 2.0)
    raise ValueError(f"reference has no metric {metric!r}")


def self_dist64(X: np.ndarray, metric: str) -> np.ndarray:
    D = dist64(X, X, metric)
    np.fill_diagonal(D, 0.0)
    return D


def is_permutation(order, n: int) -> bool:
    o = np.asarray(order)
    return o.shape == (n,) and np.array_equal(np.sort(o), np.arange(n))


def minimax64(R: np.ndarray) -> np.ndarray:
    """Minimax path distances (the iVAT image) of a dense matrix.

    Built along a Prim traversal of R: each new vertex's geodesic to an
    earlier one is the larger of its admitting edge and its parent's
    geodesic.  Minimax distances do not depend on how ties were broken.
    """
    m = R.shape[0]
    taken = np.zeros(m, bool)
    best = np.full(m, np.inf)
    parent = np.zeros(m, np.int64)
    G = np.zeros((m, m))
    v = int(np.argmax(R.max(axis=1)))
    seen = [v]
    taken[v] = True
    best = R[v].copy()
    parent[:] = v
    for _ in range(m - 1):
        u = int(np.argmin(np.where(taken, np.inf, best)))
        p = parent[u]
        idx = np.asarray(seen)
        g = np.maximum(G[p, idx], best[u])      # G[p, p] = 0
        G[u, idx] = g
        G[idx, u] = g
        taken[u] = True
        seen.append(u)
        closer = R[u] < best
        parent = np.where(closer, u, parent)
        best = np.minimum(best, R[u])
    return G


def block_score64(rstar: np.ndarray, rtol: float = 0.0):
    """(score, (k_lo, k_hi)) of a VAT image, in float64: the k_est range
    over thresholds within ``rtol`` of the threshold."""
    r = np.asarray(rstar, np.float64)
    sup = np.diagonal(r, offset=1)
    scale = r.mean() + 1e-12
    thr = max(sup.mean() + 2.0 * sup.std(), 0.5 * sup.max())
    score = float(np.clip(1.0 - sup.mean() / scale, 0.0, 1.0))
    k_lo = int(np.sum(sup > thr * (1 + rtol))) + 1
    k_hi = int(np.sum(sup > thr * (1 - rtol))) + 1
    return score, (k_lo, k_hi)


def rel_err(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape:
        return float("inf")
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)),
                                                  1e-300))


def band_groups(n: int, m: int):
    """Band sizes and middles of n ordered positions cut into m bands
    (the remainder spread over the leading bands)."""
    base, extra = divmod(n, m)
    sizes = np.full(m, base, np.int64)
    sizes[:extra] += 1
    starts = np.concatenate([[0], np.cumsum(sizes)[:-1]])
    return sizes, starts + sizes // 2


# ------------------------------------------------------ device, blocked ----

BLOCK = 512


def _pair_block(A, Y, metric):
    """(b, n) dissimilarities of a row block against all points."""
    if metric == "euclidean":
        diff = A[:, None, :] - Y[None, :, :]
        return jnp.sqrt(jnp.sum(diff * diff, axis=-1))
    # rows arrive unit-normalized for cosine
    cross = jnp.matmul(A, Y.T, precision=lax.Precision.HIGHEST)
    return jnp.clip(1.0 - cross, 0.0, 2.0)


def _row(Y, y, metric):
    """(n,) dissimilarities of one point against all points."""
    if metric == "euclidean":
        diff = Y - y[None, :]
        return jnp.sqrt(jnp.sum(diff * diff, axis=-1))
    cross = jnp.matmul(Y, y, precision=lax.Precision.HIGHEST)
    return jnp.clip(1.0 - cross, 0.0, 2.0)


@functools.partial(jax.jit, static_argnames=("metric",))
def _prim_walk(Y, order, metric):
    """Walk a given order as Prim would: before each vertex is taken, the
    shortfall of its frontier value from the least untaken one.

    Returns (largest shortfall, admitting edges e (n,) with e[0] = 0).
    """
    n = Y.shape[0]
    v0 = order[0]
    sel0 = jnp.zeros((n,), bool).at[v0].set(True)

    def body(t, carry):
        f, sel, gap, e = carry
        v = order[t]
        best = jnp.min(jnp.where(sel, jnp.inf, f))
        gap = jnp.maximum(gap, f[v] - best)
        e = e.at[t].set(f[v])
        return (jnp.minimum(f, _row(Y, Y[v], metric)), sel.at[v].set(True),
                gap, e)

    init = (_row(Y, Y[v0], metric), sel0, jnp.float32(0.0),
            jnp.zeros((n,), jnp.float32))
    _, _, gap, e = lax.fori_loop(1, n, body, init)
    return gap, e


@functools.partial(jax.jit, static_argnames=("metric",))
def _row_max(Y, metric):
    """Largest dissimilarity of every point, in row blocks."""
    n = Y.shape[0]
    nb = -(-n // BLOCK)
    Yp = jnp.pad(Y, ((0, nb * BLOCK - n), (0, 0)))

    def body(i, acc):
        A = lax.dynamic_slice_in_dim(Yp, i * BLOCK, BLOCK, 0)
        return lax.dynamic_update_slice_in_dim(
            acc, jnp.max(_pair_block(A, Y, metric), axis=1), i * BLOCK, 0)

    rm = lax.fori_loop(0, nb, body, jnp.zeros((nb * BLOCK,), jnp.float32))
    return rm[:n]


@functools.partial(jax.jit, static_argnames=("metric",))
def _prefix_edges(Y, metric):
    """Admitting edges e[p] = min over q < p of d(Y[p], Y[q])."""
    n = Y.shape[0]
    nb = -(-n // BLOCK)
    Yp = jnp.pad(Y, ((0, nb * BLOCK - n), (0, 0)))
    cols = jnp.arange(n)

    def body(i, e):
        A = lax.dynamic_slice_in_dim(Yp, i * BLOCK, BLOCK, 0)
        rows = i * BLOCK + jnp.arange(BLOCK)
        P = _pair_block(A, Y, metric)
        P = jnp.where(cols[None, :] < rows[:, None], P, jnp.inf)
        eb = jnp.where((rows > 0) & (rows < n), jnp.min(P, axis=1), 0.0)
        return lax.dynamic_update_slice_in_dim(e, eb, i * BLOCK, 0)

    e = lax.fori_loop(0, nb, body, jnp.zeros((nb * BLOCK,), jnp.float32))
    return e[:n]


def prepared(X: np.ndarray, metric: str):
    """The table prepared for the device passes: centred in float64
    (euclidean) or unit-normalized (cosine), then float32."""
    Y = np.asarray(X, np.float64)
    if metric == "euclidean":
        Y = Y - Y.mean(axis=0)
    else:
        Y = Y / np.linalg.norm(Y, axis=1, keepdims=True)
    return jnp.asarray(Y.astype(np.float32))


def prim_gap_device(X: np.ndarray, order: np.ndarray, metric: str) -> float:
    """``order_gap`` of a full-table order, on the device."""
    Y = prepared(X, metric)
    gap, e = jax.device_get(_prim_walk(Y, jnp.asarray(order), metric))
    rowmax = np.asarray(jax.device_get(_row_max(Y, metric)), np.float64)
    seed = rowmax.max() - rowmax[int(order[0])]
    return float(max(float(gap), seed)
                 / max(np.mean(e[1:], dtype=np.float64), 1e-300))


@functools.partial(jax.jit, static_argnames=("metric",))
def _nearest_other(Y, comp, metric):
    """Per point, the nearest point of another component: (dist, index).
    Ties go to the lower index."""
    n = Y.shape[0]
    nb = -(-n // BLOCK)
    Yp = jnp.pad(Y, ((0, nb * BLOCK - n), (0, 0)))
    cp = jnp.pad(comp, (0, nb * BLOCK - n), constant_values=-1)

    def body(i, carry):
        w, j = carry
        A = lax.dynamic_slice_in_dim(Yp, i * BLOCK, BLOCK, 0)
        ca = lax.dynamic_slice_in_dim(cp, i * BLOCK, BLOCK, 0)
        P = jnp.where(comp[None, :] == ca[:, None], jnp.inf,
                      _pair_block(A, Y, metric))
        w = lax.dynamic_update_slice_in_dim(w, jnp.min(P, axis=1),
                                            i * BLOCK, 0)
        j = lax.dynamic_update_slice_in_dim(
            j, jnp.argmin(P, axis=1).astype(jnp.int32), i * BLOCK, 0)
        return w, j

    w, j = lax.fori_loop(0, nb, body,
                         (jnp.zeros((nb * BLOCK,), jnp.float32),
                          jnp.zeros((nb * BLOCK,), jnp.int32)))
    return w[:n], j[:n]


def mst_weight_device(Y, metric: str) -> float:
    """Exact minimum spanning tree weight of the prepared points, by
    Boruvka rounds: each round finds every point's nearest point in
    another component on the device and joins components on the host."""
    n = Y.shape[0]
    parent = np.arange(n)

    def root(a):
        while parent[a] != a:
            parent[a] = parent[parent[a]]
            a = parent[a]
        return a

    total = 0.0
    comp = np.arange(n, dtype=np.int32)
    while len(np.unique(comp)) > 1:
        w, j = jax.device_get(_nearest_other(Y, jnp.asarray(comp), metric))
        w = w.astype(np.float64)
        # the lightest edge out of each component, ties by (u, v)
        u = np.lexsort((j, np.arange(n), w))
        seen = set()
        for a in u:
            c = comp[a]
            if c in seen:
                continue
            seen.add(c)
            ra, rb = root(a), root(int(j[a]))
            if ra != rb:
                parent[max(ra, rb)] = min(ra, rb)
                total += float(w[a])
        comp = np.asarray([root(a) for a in range(n)], np.int32)
    return total


def approx_order_numbers(X: np.ndarray, order: np.ndarray, metric: str,
                         tree_weight: float) -> dict:
    """``order_bound`` and ``order_excess`` of an approx order."""
    Yc = prepared(X, metric)
    e = jax.device_get(_prefix_edges(Yc[jnp.asarray(order)], metric))
    total = float(np.sum(e, dtype=np.float64))
    exact = mst_weight_device(Yc, metric)
    return {"order_bound": (total - tree_weight) / tree_weight,
            "order_excess": (total - exact) / exact}


# ------------------------------------------------------------ answers ----

def check_band(X: np.ndarray, order: np.ndarray, rstar, ivat, sample_idx,
               group_sizes, metric: str):
    """Numbers of a band render (flashvat and approx rungs)."""
    n = X.shape[0]
    m = np.asarray(rstar).shape[0]
    sizes, mids = band_groups(n, m)
    rep = np.asarray(order)[mids]
    exact = (np.array_equal(np.asarray(sample_idx), rep)
             and np.array_equal(np.asarray(group_sizes), sizes))
    R = self_dist64(X[rep], metric)
    return {"band_layout": 0.0 if exact else 1.0,
            "image_err": max(rel_err(rstar, R), rel_err(ivat, minimax64(R)))}


def check_report(report: dict, rstar_ref: np.ndarray):
    """The exact checks of an ``assess()`` report against the reference
    image."""
    _, (k_lo, k_hi) = block_score64(rstar_ref, THRESHOLD_RTOL)
    h = float(report["hopkins"])
    rule = (h > 0.75) and (float(report["block_score"]) > 0.3)
    exact = (k_lo <= int(report["k_est"]) <= k_hi and 0.0 < h < 1.0
             and bool(report["clustered"]) == rule)
    return {"report_rules": 0.0 if exact else 1.0}
