"""Approximate VAT via a kNN-graph Borůvka MST — the million-point rung.

Exact VAT is a Prim traversal of the complete graph: O(n²·d) work no
matter how well it streams (the Turbo engine's ceiling is ~100k points
on CPU).  This rung trades exactness for scale the way tmap does for
molecular maps: build a sparse kNN graph (O(n·k) edges), take ITS
minimum spanning tree with Borůvka's algorithm, and traverse that tree
in Prim order to get a VAT ordering.  The kNN-MST weight is always >=
the true MST weight (it spans using a subset of edges), with equality
exactly when the true MST is contained in the kNN graph — at k = n-1
the two pipelines coincide, which is the oracle the property suite
certifies against.

Stages:

  * kNN graph — ``kernels.ops.knn_graph`` (blocked/Pallas, exact) below
    ``EXACT_KNN_N``, else ``knn_graph_anchored``: an IVF-style two-level
    search (random anchors ≈ sqrt(n), a blocked assignment pass, brute
    force within each point's ``probes`` nearest anchor cells) that
    keeps every intermediate O(n·probes·k) — brute-force kNN at 1M
    points would be 10^13 flops; the anchored pass is ~10^10.
  * Borůvka — ``_boruvka_pass`` is one jittable fold: symmetrize the
    directed kNN list (each entry contributes (u→v) and (v→u) sharing
    ONE weight, so every component sees every incident edge under a
    globally consistent key), pick each component's minimum incident
    cross edge by a three-stage lexicographic ``segment_min`` on
    (w, min-endpoint, max-endpoint) — x64 is disabled, so no packed
    64-bit keys — hook components along the picks, break the resulting
    2-cycles toward the smaller root, and collapse labels by pointer
    jumping.  Distinct lexicographic keys make cycles longer than 2
    impossible (keys are non-increasing around any hooking cycle, so
    all hops share one key = one edge pair), which is what lets the
    pointer-jump ``while_loop`` terminate unconditionally.  A host loop
    re-invokes the pass until no component finds a cross edge —
    Borůvka halves the component count per pass, so ≤ ceil(log2 n)+2
    iterations.
  * connectivity repair — a kNN graph need not be connected (separated
    blobs with small k never are).  The surviving components are
    spliced with per-component fallback edges: the minimum-index vertex
    represents each component, and an exact host-side Prim over the
    representatives' true pairwise dissimilarities supplies C-1 real
    edges (a chain over representatives past ``REPAIR_MAX_C``, where
    the (C, C) matrix would defeat the memory story).  The repair is
    reported in ``ApproxStats`` — it is the spanning-defect estimate.
  * ordering — ``mst_vat_order``: a host heap Prim restricted to the
    tree's n-1 edges.  The heap key (weight, vertex) reproduces exact
    Prim's first-index tie rule, so on the full graph (k = n-1) the
    ordering is identical to ``core.vat.vat_matrix_free``'s given the
    same seed.  The default seed is the vertex with the largest k-NN
    radius — at k = n-1 that IS exact VAT's "argmax of row max" rule.
"""
from __future__ import annotations

import dataclasses
import functools
import heapq
import math
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp

from repro import spans
from repro.kernels import ops as kops
from repro.kernels.ref import check_metric, pairwise_dissim_ref

#: Largest n the auto mode serves with exact blocked kNN (O(n²·d) work);
#: past it the anchored two-level search keeps the build near-linear.
EXACT_KNN_N = 32_768

#: Largest surviving-component count repaired with an exact Prim over
#: the (C, C) representative matrix; past it a representative chain
#: keeps repair memory O(C).
REPAIR_MAX_C = 4_096


@dataclasses.dataclass(frozen=True)
class ApproxStats:
    """The approx rung's error-model report (rides on ``ResultMeta``).

    Attributes:
      k: neighbours per point actually used (min(k, n-1)).
      mode: "exact" (blocked brute-force kNN) or "anchored" (two-level).
      n_passes: Borůvka passes until no cross edge remained.
      components: kNN-graph components before repair (1 = no defect).
      repaired_edges: fallback edges spliced in (= components - 1).
      mst_weight: total tree weight, repair included (f64 sum).  Always
        >= the exact MST weight; the ratio against exact is the
        quality row ``benchmarks.bench`` reports on overlap sizes.
      repair_weight: weight contributed by the fallback edges alone —
        together with ``repaired_edges`` this is the spanning-defect
        estimate (0.0 means the kNN graph already spanned).
    """

    k: int
    mode: str
    n_passes: int
    components: int
    repaired_edges: int
    mst_weight: float
    repair_weight: float


class MSTEdges(NamedTuple):
    """A spanning tree as parallel host arrays (n-1 edges when spanning)."""
    src: np.ndarray      # (m,) int32
    dst: np.ndarray      # (m,) int32
    weight: np.ndarray   # (m,) float32


class ApproxVATResult(NamedTuple):
    """Approximate VAT ordering + its MST edge trace + the error report."""
    order: np.ndarray    # (n,) int32 — visit order
    edges: np.ndarray    # (n,) float32 — per-visit tree edge (edges[0]=0)
    stats: ApproxStats


@jax.jit
def _boruvka_pass(comp, src, dst, w):
    """One Borůvka round: per-component min cross edge, hook, collapse.

    Args:
      comp: (n,) int32 — current component label per vertex (a vertex id;
        label arrays double as the union-find forest).
      src, dst: (m,) int32 — directed edge endpoints, both directions
        present, self-loops allowed (they mask out as cu == cv).
      w: (m,) float32 — edge weights, identical for the two directions
        of one edge (the caller's symmetrization guarantees it).

    Returns:
      (new_comp (n,) i32, va (n,) i32, vb (n,) i32, ew (n,) f32,
       rec (n,) bool): per component-root c, the selected edge
      (va[c], vb[c], ew[c]) and whether to record it (rec — False for
      rootless indices and the dropped side of each 2-cycle).
    """
    n = comp.shape[0]
    iota = jnp.arange(n, dtype=jnp.int32)
    cu = comp[src]
    cv = comp[dst]
    wm = jnp.where(cu != cv, w, jnp.inf)
    amin = jnp.minimum(src, dst)
    amax = jnp.maximum(src, dst)
    # Lexicographic (w, amin, amax) segment-min, one stage per field —
    # ties on w resolve to one concrete edge pair, which is what rules
    # out hooking cycles longer than 2.
    m1 = jax.ops.segment_min(wm, cu, num_segments=n)
    e1 = wm == m1[cu]
    m2 = jax.ops.segment_min(jnp.where(e1, amin, n), cu, num_segments=n)
    e2 = e1 & (amin == m2[cu])
    m3 = jax.ops.segment_min(jnp.where(e2, amax, n), cu, num_segments=n)
    has = jnp.isfinite(m1)
    va = jnp.where(has, m2, 0).astype(jnp.int32)
    vb = jnp.where(has, m3, 0).astype(jnp.int32)
    ca = comp[va]
    cb = comp[vb]
    parent = jnp.where(has, jnp.where(ca == iota, cb, ca), iota)
    # 2-cycle break: both sides picked the same edge; keep the smaller
    # root, drop the larger side's copy (equal keys => equal weights, so
    # the recorded weight sum is unaffected).
    drop = has & (parent[parent] == iota) & (iota < parent)
    parent = jnp.where(drop, iota, parent)
    parent = jax.lax.while_loop(
        lambda p: jnp.any(p != p[p]), lambda p: p[p], parent)
    return parent[comp], va, vb, jnp.where(has, m1, 0.0), has & ~drop


def _prim_edges_np(R: np.ndarray) -> list[tuple[int, int, float]]:
    """Exact MST edge list of a dense dissimilarity matrix (host Prim).

    O(C²) numpy — the connectivity-repair solver and the small-n oracle
    the property suite compares Borůvka against.  First-index
    tie-breaking via np.argmin, matching the exact engine's rule.
    """
    C = R.shape[0]
    in_tree = np.zeros(C, bool)
    in_tree[0] = True
    best = R[0].astype(np.float64).copy()
    best_from = np.zeros(C, np.int64)
    edges = []
    for _ in range(C - 1):
        cand = np.where(in_tree, np.inf, best)
        v = int(np.argmin(cand))
        edges.append((int(best_from[v]), v, float(best[v])))
        in_tree[v] = True
        upd = R[v] < best
        best_from = np.where(upd, v, best_from)
        best = np.where(upd, R[v], best)
    return edges


def _rowwise_dissim_np(A: np.ndarray, B: np.ndarray, metric: str):
    """Per-row dissimilarity of paired points (repair-chain fallback)."""
    A = A.astype(np.float32)
    B = B.astype(np.float32)
    if metric == "sqeuclidean":
        return np.sum((A - B) ** 2, axis=1)
    if metric == "euclidean":
        return np.sqrt(np.sum((A - B) ** 2, axis=1))
    if metric == "manhattan":
        return np.sum(np.abs(A - B), axis=1)
    na = np.sqrt(np.sum(A * A, axis=1))
    nb = np.sqrt(np.sum(B * B, axis=1))
    denom = np.maximum(na * nb, 1e-12)
    return np.clip(1.0 - np.sum(A * B, axis=1) / denom, 0.0, 2.0)


@spans.span("approx.boruvka")
def boruvka_mst(idx, dist, *, X=None, metric: str = "euclidean"):
    """MST of a directed kNN graph + connectivity repair.

    Args:
      idx: (n, k) int — per-row neighbour indices; self-loops mark
        invalid slots and are ignored.
      dist: (n, k) float — matching dissimilarities.  Each directed
        entry is symmetrized in here (both directions share its weight),
        so duplicate (u, v)/(v, u) discoveries become parallel edges of
        a multigraph rather than an inconsistently-weighted edge.
      X: (n, d) float or None — required only when the graph turns out
        disconnected (repair recomputes true representative distances).
      metric: one of ``kernels.ref.METRICS`` (repair edges only).

    Returns:
      (MSTEdges, n_passes, components, repair_weight): the spanning
      edge list (always n-1 edges — repair guarantees it), the Borůvka
      pass count, the pre-repair component count, and the repair's
      weight contribution.
    """
    check_metric(metric)
    idx = spans.readback(idx, np.int32)
    n, k = idx.shape
    rows = np.repeat(np.arange(n, dtype=np.int32), k)
    flat_i = idx.ravel()
    flat_d = spans.readback(dist, np.float32).ravel()
    src = jnp.asarray(np.concatenate([rows, flat_i]))
    dst = jnp.asarray(np.concatenate([flat_i, rows]))
    w = jnp.asarray(np.concatenate([flat_d, flat_d]))

    comp = jnp.arange(n, dtype=jnp.int32)
    es, ed, ew = [], [], []
    passes = 0
    cap = int(math.ceil(math.log2(max(n, 2)))) + 2
    while passes < cap:
        comp, va, vb, pw, rec = _boruvka_pass(comp, src, dst, w)
        recn = spans.readback(rec)
        if not recn.any():
            break
        passes += 1
        es.append(spans.readback(va)[recn])
        ed.append(spans.readback(vb)[recn])
        ew.append(spans.readback(pw)[recn])

    comp_np = spans.readback(comp)
    roots = np.unique(comp_np)
    ncomp = int(roots.size)
    repair_w = 0.0
    if ncomp > 1:
        if X is None:
            raise ValueError(
                "kNN graph is disconnected; pass X so the spanning repair "
                "can compute fallback edges")
        Xn = spans.readback(X, np.float32)
        reps = np.full(n, n, np.int64)
        np.minimum.at(reps, comp_np, np.arange(n))
        reps = reps[roots]                       # min vertex per component
        if ncomp <= REPAIR_MAX_C:
            R = spans.readback(kops.pairwise_dist(jnp.asarray(Xn[reps]),
                                                  metric=metric))
            extra = _prim_edges_np(R)
            ra = reps[[a for a, _, _ in extra]]
            rb = reps[[b for _, b, _ in extra]]
            rw = np.asarray([wgt for _, _, wgt in extra], np.float32)
        else:  # too many islands for a (C, C) matrix: chain them
            ra, rb = reps[:-1], reps[1:]
            rw = _rowwise_dissim_np(Xn[ra], Xn[rb], metric).astype(np.float32)
        es.append(ra.astype(np.int32))
        ed.append(rb.astype(np.int32))
        ew.append(rw)
        repair_w = float(np.sum(rw, dtype=np.float64))

    if es:
        tree = MSTEdges(np.concatenate(es).astype(np.int32),
                        np.concatenate(ed).astype(np.int32),
                        np.concatenate(ew).astype(np.float32))
    else:  # n == 1
        tree = MSTEdges(np.empty(0, np.int32), np.empty(0, np.int32),
                        np.empty(0, np.float32))
    return tree, passes, ncomp, repair_w


@spans.span("approx.walk")
def mst_vat_order(n: int, tree: MSTEdges, i0: int):
    """VAT ordering of a spanning tree: Prim restricted to tree edges.

    On a tree, Prim's traversal from any vertex visits every vertex by
    its unique lightest connection to the visited set — the heap key
    (weight, vertex) reproduces exact Prim's (min value, first index)
    tie rule, so restricted to the TRUE MST this equals full-graph
    Prim's order for the same seed.

    Args:
      n: vertex count.
      tree: spanning edge list (n-1 edges).
      i0: seed vertex.

    Returns:
      (order (n,) int32, edges (n,) float32) — visit order and each
      visit's tree edge weight (edges[0] = 0), the same trace shape as
      ``core.vat.FlashVATResult``.
    """
    starts = np.concatenate([tree.src, tree.dst]).astype(np.int64)
    ends = np.concatenate([tree.dst, tree.src]).astype(np.int64)
    ws = np.concatenate([tree.weight, tree.weight]).astype(np.float64)
    perm = np.argsort(starts, kind="stable")
    ends = ends[perm]
    ws = ws[perm]
    off = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(starts, minlength=n), out=off[1:])

    order = np.empty(n, np.int32)
    edges = np.zeros(n, np.float32)
    visited = np.zeros(n, bool)
    best = np.full(n, np.inf)
    best[i0] = 0.0
    heap = [(0.0, int(i0))]
    t = 0
    while heap and t < n:
        wv, v = heapq.heappop(heap)
        if visited[v] or wv > best[v]:
            continue
        visited[v] = True
        order[t] = v
        edges[t] = wv
        t += 1
        for e in range(off[v], off[v + 1]):
            u = int(ends[e])
            if not visited[u] and ws[e] < best[u]:
                best[u] = ws[e]
                heapq.heappush(heap, (float(ws[e]), u))
    if t < n:  # unreachable once repair guarantees spanning; keep total
        rest = np.flatnonzero(~visited)
        order[t:] = rest
        edges[t:] = 0.0
    return order, edges


def _bucket(size: int) -> int:
    """Next power of two >= size (floor 8) — the cell-shape bucketing
    that bounds the jit cache: cells come in every size, and compiling
    per exact shape would dominate the whole anchored pass."""
    b = 8
    while b < size:
        b <<= 1
    return b


#: Bytes one ``_cell_topk`` dispatch gathers and tiles over its cells
#: (f32 query and candidate rows, distance tiles).  Sets the cells per
#: dispatch of each bucket pair (``_cells_per_dispatch``).
_CELL_DISPATCH_BYTES = 256 << 20


def _cells_per_dispatch(qp: int, cp: int, d: int) -> int:
    """Cells of one (qp, cp) bucket pair that one ``_cell_topk`` call
    takes: the byte budget over one cell's f32 rows and tile, rounded
    down to a power of two (at least 1).  A function of the bucket pair
    and d alone, so each pair compiles one program."""
    g = max(1, _CELL_DISPATCH_BYTES // (4 * ((qp + cp) * d + qp * cp)))
    return 1 << (g.bit_length() - 1)


@functools.partial(jax.jit, static_argnames=("metric", "kk"),
                   donate_argnames=("part_d", "part_i"))
def _cell_topk(X, qid, slot, cid, cells, part_d, part_i, *, metric: str,
               kk: int):
    """Top-kk candidates per query for a chunk of G (padded) anchor cells,
    written into the per-(point, probe slot) partial lists.

    The rows come from the device-resident table ``X`` by index: only
    the int32 id arrays cross from the host.  A loop over the chunk's
    first ``cells`` cells brute-forces one cell at a time, so the padded
    cells that fill the chunk up to G are never visited.  Padded
    candidate columns carry cid = -1 and padded query rows qid = -2
    (distinct sentinels so padding never self-matches); both gather row
    0, padded columns mask to +inf before the top_k, so they can only
    fill trailing slots of undersized cells, which read (inf, -1), and
    a padded query row writes nothing.  Memory is one cell's rows and
    tile, O(qp · (d + cp)), plus the O(G · (qp + cp)) ids — nothing
    (n, n).

    Args:
      X: (n, d) f32 — the table.
      qid, slot: (G, qp) int32 — query point ids and the probe slot
        through which each query reaches its cell.
      cid: (G, cp) int32 — candidate point ids (the cell's members).
      cells: int32 scalar — how many of the G cells are real.
      part_d, part_i: (n, probes, k) f32 / int32 — partial lists,
        updated in place (donated); a cell fills ``[q, slot, :kk]``.
    """
    n = X.shape[0]

    def one_cell(g, parts):
        q, c = qid[g], cid[g]
        D = pairwise_dissim_ref(X[jnp.maximum(q, 0)], X[jnp.maximum(c, 0)],
                                metric=metric)
        bad = (c[None, :] < 0) | (c[None, :] == q[:, None])
        neg, p = jax.lax.top_k(-jnp.where(bad, jnp.inf, D), kk)
        gd = -neg
        gi = jnp.where(jnp.isfinite(gd), jnp.take(c, p), -1)
        r = jnp.where(q >= 0, q, n)          # padded rows fall off the end
        pd, pi = parts
        return (pd.at[r, slot[g], :kk].set(gd, mode="drop"),
                pi.at[r, slot[g], :kk].set(gi, mode="drop"))

    return jax.lax.fori_loop(0, cells, one_cell, (part_d, part_i))


def knn_graph_anchored(X, *, k: int, metric: str = "euclidean",
                       anchors: int | None = None, probes: int = 2,
                       use_pallas: bool = False, assign_block: int = 8_192,
                       rng: np.random.Generator | None = None):
    """Approximate kNN graph by two-level (IVF-style) search.

    Sample ``anchors`` random points (≈ sqrt(n) by default — random
    anchors track data density, so cell sizes concentrate near
    n/anchors), assign every point to its ``probes`` nearest anchors in
    a blocked pass, then brute-force each anchor cell: the candidates
    are the cell's primary members, the queries everyone probing it.
    Probe pools are disjoint (primary assignment partitions the data),
    so the per-point merge over probes needs no dedup.

    The table stays on the device (a host table is uploaded once).  The
    host reads the assignment back once and keeps only integer
    bookkeeping: per cell, padded query and candidate id arrays.  Cells
    are grouped by their (query, candidate) bucket pair and brute-forced
    in chunks of ``_cells_per_dispatch`` cells by ``_cell_topk``, which
    gathers the rows on the device; every chunk is launched before the
    partial lists are read back once.  Every intermediate is
    O(assign_block · anchors) or one cell's rows and tile,
    O(qp · (d + cp)) — nothing (n, n); the partial lists are
    O(n · probes · k).

    Recall is the usual IVF story: a true neighbour is missed only when
    it lives in none of the probed cells; the Borůvka stage's repair
    covers the resulting (rare) disconnections.

    Args:
      X: (n, d) float — data points, a host or device array (the blocked
        passes go through ``kernels.ops.pairwise_dist``).
      k: neighbours per point.
      metric: one of ``kernels.ref.METRICS``.
      anchors: cell count; None = max(32, round(sqrt(n))).
      probes: anchor cells searched per point.
      use_pallas: forwarded to the distance tiles.
      assign_block: rows per assignment-pass tile.
      rng: anchor-sampling generator (default_rng(0) when None).

    Returns:
      (dist (n, k) f32, idx (n, k) i64) host arrays — ascending per row;
      slots the probed cells could not fill hold (inf, -1).
    """
    check_metric(metric)
    X = jnp.asarray(X, jnp.float32)
    n, d = X.shape
    c = anchors if anchors is not None else max(32, int(round(math.sqrt(n))))
    c = min(c, n)
    probes = max(1, min(probes, c))
    rng = rng if rng is not None else np.random.default_rng(0)
    aidx = rng.choice(n, size=c, replace=False)
    A = X[jnp.asarray(aidx)]

    pids = []
    for s0 in range(0, n, assign_block):
        rows = min(assign_block, n - s0)
        if rows == assign_block:
            xb = jax.lax.dynamic_slice_in_dim(X, s0, assign_block)
        else:  # keep one eager shape for the whole pass
            xb = jnp.pad(X[s0:], ((0, assign_block - rows), (0, 0)))
        D = kops.pairwise_dist(xb, A, metric=metric, use_pallas=use_pallas)
        pids.append(jax.lax.top_k(-D, probes)[1])
    probe_idx = spans.readback(jnp.concatenate(pids), np.int32)[:n]

    # CSR views: candidates by primary cell, queries by each probe slot.
    primary = probe_idx[:, 0]
    by_cell = np.argsort(primary, kind="stable")
    start = np.concatenate([[0],
                            np.cumsum(np.bincount(primary, minlength=c))])
    q_order = [np.argsort(probe_idx[:, s], kind="stable")
               for s in range(probes)]
    q_start = [np.concatenate(
        [[0], np.cumsum(np.bincount(probe_idx[:, s], minlength=c))])
        for s in range(probes)]

    groups: dict[tuple[int, int], list] = {}
    for g in range(c):
        cand = by_cell[start[g]:start[g + 1]]
        if cand.size == 0:
            continue
        qs = [q_order[s][q_start[s][g]:q_start[s][g + 1]]
              for s in range(probes)]
        q = np.concatenate(qs)
        if q.size == 0:
            continue
        slot = np.concatenate(
            [np.full(x.size, s, np.int32) for s, x in enumerate(qs)])
        groups.setdefault((_bucket(q.size), _bucket(cand.size)), []).append(
            (q, slot, cand))

    part_d = jnp.full((n, probes, k), jnp.inf, jnp.float32)
    part_i = jnp.full((n, probes, k), -1, jnp.int32)
    for (qp, cp), group in groups.items():
        G = _cells_per_dispatch(qp, cp, d)
        for c0 in range(0, len(group), G):
            chunk = group[c0:c0 + G]
            qid = np.full((G, qp), -2, np.int32)
            slots = np.zeros((G, qp), np.int32)
            cid = np.full((G, cp), -1, np.int32)
            for j, (q, slot, cand) in enumerate(chunk):
                qid[j, :q.size] = q
                slots[j, :q.size] = slot
                cid[j, :cand.size] = cand
            part_d, part_i = _cell_topk(
                X, qid, slots, cid, np.int32(len(chunk)), part_d, part_i,
                metric=metric, kk=min(k, cp))

    flat_d = spans.readback(part_d, np.float32).reshape(n, probes * k)
    flat_i = spans.readback(part_i, np.int64).reshape(n, probes * k)
    sel = np.argsort(flat_d, axis=1, kind="stable")[:, :k]
    return (np.take_along_axis(flat_d, sel, axis=1),
            np.take_along_axis(flat_i, sel, axis=1))


def approx_vat(X, *, k: int = 15, metric: str = "euclidean",
               knn_mode: str = "auto", probes: int = 2,
               use_pallas: bool = False, block: int | None = None,
               anchors: int | None = None, seed_vertex: int | None = None,
               rng: np.random.Generator | None = None) -> ApproxVATResult:
    """kNN-graph Borůvka VAT — the whole approximate pipeline.

    Args:
      X: (n, d) float — data points.
      k: neighbours per point — THE error-bound knob.  The kNN-MST
        weight is non-increasing in k (larger k gives a supergraph) and
        reaches the exact MST weight at k = n-1; ``docs/scaling.md``
        has the choosing-k guidance.
      metric: one of ``kernels.ref.METRICS``.
      knn_mode: "auto" (exact blocked kNN up to ``EXACT_KNN_N``, then
        anchored), "exact", or "anchored".
      probes / anchors: anchored-search knobs (see
        ``knn_graph_anchored``).
      use_pallas: forwarded to every distance tile.
      block: kNN tile edge override (None = per-path default).
      seed_vertex: traversal seed; None picks the vertex with the
        largest k-NN radius — at k = n-1 this is exactly the exact
        engine's argmax-of-row-max seed rule.
      rng: anchor sampling generator (anchored mode only).

    Returns:
      ``ApproxVATResult`` (order, per-visit edge trace, ``ApproxStats``).
    """
    check_metric(metric)
    if knn_mode not in ("auto", "exact", "anchored"):
        raise ValueError(f"knn_mode must be auto|exact|anchored, "
                         f"got {knn_mode!r}")
    Xn = spans.readback(X, np.float32)
    n = Xn.shape[0]
    if n == 1:
        stats = ApproxStats(k=0, mode="exact", n_passes=0, components=1,
                            repaired_edges=0, mst_weight=0.0,
                            repair_weight=0.0)
        return ApproxVATResult(np.zeros(1, np.int32), np.zeros(1, np.float32),
                               stats)
    k_eff = min(k, n - 1)
    exact = knn_mode == "exact" or (knn_mode == "auto" and n <= EXACT_KNN_N)
    # the kNN stage works on the device table (a host X is uploaded
    # once); the host copy serves the Borůvka repair
    Xd = jnp.asarray(X if isinstance(X, jax.Array) else Xn, jnp.float32)
    with spans.span("approx.knn"):
        if exact:
            dj, ij = kops.knn_graph(Xd, k=k_eff, metric=metric,
                                    use_pallas=use_pallas, block=block)
            dist = spans.readback(dj)
            idx = spans.readback(ij, np.int64)
            mode = "exact"
        else:
            dist, idx = knn_graph_anchored(Xd, k=k_eff, metric=metric,
                                           anchors=anchors, probes=probes,
                                           use_pallas=use_pallas, rng=rng)
            mode = "anchored"

    finite = np.isfinite(dist) & (idx >= 0)
    radius = np.where(finite, dist, -np.inf).max(axis=1)
    i0 = int(seed_vertex) if seed_vertex is not None \
        else int(np.argmax(radius))
    rows = np.arange(n, dtype=np.int64)
    idx = np.where(finite, idx, rows[:, None]).astype(np.int32)
    dist = np.where(finite, dist, 0.0).astype(np.float32)

    tree, passes, ncomp, repair_w = boruvka_mst(idx, dist, X=Xn,
                                               metric=metric)
    order, edges = mst_vat_order(n, tree, i0)
    stats = ApproxStats(
        k=k_eff, mode=mode, n_passes=passes, components=ncomp,
        repaired_edges=max(ncomp - 1, 0),
        mst_weight=float(np.sum(tree.weight, dtype=np.float64)),
        repair_weight=repair_w)
    return ApproxVATResult(order, edges, stats)
