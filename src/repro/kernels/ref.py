"""Pure-jnp oracles for every Pallas kernel in this package.

These are the correctness references the kernel tests sweep against, and
also the production XLA fallback path (they jit and shard fine — the
Pallas kernels exist to beat them on TPU, not to replace them).

Metric support: VAT is defined on an arbitrary pairwise *dissimilarity*
matrix, so the distance oracles are metric-dispatched.  ``METRICS`` is
the canonical tuple of computable metrics; ``"precomputed"`` (the user
hands the matrix in directly) is an API-layer concept and never reaches
this module.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.numerics.condition import check_form

#: Metrics every pairwise path (XLA ref + Pallas tile) implements.
METRICS = ("euclidean", "sqeuclidean", "manhattan", "cosine")


#: Precision of every Gram dot (this module's matmuls, the pairwise
#: tile, the Prim pivot rows).  A TPU runs an f32 dot at the default
#: precision as one bf16 pass, which leaves ~3 significant digits in
#: every Gram cross term; HIGHEST keeps f32 on every backend (the CPU
#: computes f32 either way).  Read at trace time;
#: ``benchmarks/precision_control.py`` lowers it to show why it is set.
GRAM_PRECISION = jax.lax.Precision.HIGHEST


def _dot(a: jax.Array, b: jax.Array) -> jax.Array:
    """f32 matmul at ``GRAM_PRECISION``."""
    return jnp.matmul(a, b, precision=GRAM_PRECISION)


def check_metric(metric: str):
    """Raise ValueError unless ``metric`` names a computable metric.

    The one canonical check every pairwise path (refs and Pallas
    wrappers) shares — keep error wording and the accepted set here.
    """
    if metric not in METRICS:
        raise ValueError(f"metric must be one of {METRICS}, got {metric!r}")


def pairwise_dissim_ref(X: jax.Array, Y: jax.Array | None = None, *,
                        metric: str = "euclidean",
                        form: str = "gram") -> jax.Array:
    """Metric-dispatched pairwise dissimilarity matrix.

    Args:
      X: (n, d) float — query points.
      Y: (m, d) float or None — reference points (None: Y = X).
      metric: one of ``METRICS``.
        euclidean    ||xi - yj||_2          (Gram trick, one MXU matmul)
        sqeuclidean  ||xi - yj||_2^2        (same, no sqrt)
        manhattan    sum_k |xik - yjk|      (broadcast |diff| reduce)
        cosine       1 - xi.yj/(|xi||yj|)   (in [0, 2]; zero-norm rows
                                             get an eps-guarded denom)
      form: "gram" (default — the MXU decomposition above, absolute
        cancellation error ~eps·max||x||²) or "direct" — squared
        differences ``sum_k (xik - yjk)²``, no cancellation, relative
        error only.  Selected by ``numerics.resolve``; only meaningful
        for euclidean/sqeuclidean (manhattan is already direct, cosine
        has no direct form and ignores it).

    Returns:
      (n, m) float32 dissimilarity matrix.
    """
    check_metric(metric)
    check_form(form)
    if Y is None:
        Y = X
    Xf = X.astype(jnp.float32)
    Yf = Y.astype(jnp.float32)
    if metric in ("euclidean", "sqeuclidean"):
        if form == "direct":
            # No cancellation: every term is a squared difference, so the
            # error is relative to the distance itself.  Same formula as
            # every other direct-form ref in this module — ref↔ref rows
            # stay bitwise-identical, the property the matrix-free
            # ordering contracts need under the safe/auto policies.
            diff = Xf[:, None, :] - Yf[None, :, :]
            sq = jnp.sum(diff * diff, axis=-1)
        else:
            # ||xi - yj||^2 = ||xi||^2 + ||yj||^2 - 2 xi.yj — the cross
            # term is one matmul, which is what makes this MXU-friendly
            # (and is the exact decomposition the Pallas kernel tiles).
            nx = jnp.sum(Xf * Xf, axis=-1)
            ny = jnp.sum(Yf * Yf, axis=-1)
            sq = jnp.maximum(
                nx[:, None] + ny[None, :] - 2.0 * _dot(Xf, Yf.T), 0.0)
        return jnp.sqrt(sq) if metric == "euclidean" else sq
    if metric == "manhattan":
        return jnp.sum(jnp.abs(Xf[:, None, :] - Yf[None, :, :]), axis=-1)
    # cosine
    cross = _dot(Xf, Yf.T)
    nx = jnp.sqrt(jnp.sum(Xf * Xf, axis=-1))
    ny = jnp.sqrt(jnp.sum(Yf * Yf, axis=-1))
    denom = jnp.maximum(nx[:, None] * ny[None, :], 1e-12)
    return jnp.clip(1.0 - cross / denom, 0.0, 2.0)


def pairwise_dist_ref(X: jax.Array, Y: jax.Array | None = None) -> jax.Array:
    """Euclidean distance matrix via the Gram trick (legacy name).

    Kept as the stable spelling older call sites and tests use;
    ``pairwise_dissim_ref`` is the metric-dispatched front door.
    """
    return pairwise_dissim_ref(X, Y, metric="euclidean")


def knn_graph_ref(X: jax.Array, *, k: int,
                  metric: str = "euclidean") -> tuple[jax.Array, jax.Array]:
    """k nearest neighbours of every point — the materializing oracle.

    Small-n correctness reference for ``kernels/knn_graph.py``: builds the
    full (n, n) dissimilarity matrix (so never use it past a few thousand
    points), masks the diagonal, and takes the k smallest per row via
    ``lax.top_k`` on negated values.  XLA's top_k breaks ties toward the
    lower index, which is exactly the selection order of the blocked
    paths' (value, position) fold — the tie contract every kNN path in
    this package shares.

    Args:
      X: (n, d) float — data points.
      k: neighbours per point; must satisfy 1 <= k <= n - 1.
      metric: one of ``METRICS``.

    Returns:
      (dist (n, k) f32 ascending per row, idx (n, k) i32) — idx[i, 0] is
      i's nearest neighbour; the point itself is never its own neighbour.
    """
    check_metric(metric)
    n = X.shape[0]
    if not 1 <= k <= n - 1:
        raise ValueError(f"k must satisfy 1 <= k <= n-1 = {n - 1}, got {k}")
    R = pairwise_dissim_ref(X, metric=metric)
    R = jnp.where(jnp.eye(n, dtype=bool), jnp.inf, R)
    neg, idx = jax.lax.top_k(-R, k)
    return -neg, idx.astype(jnp.int32)


def row_dissim_ref(X: jax.Array, x: jax.Array, *,
                   metric: str = "euclidean") -> jax.Array:
    """Dissimilarity of every row of X to a single point x.

    The O(n) building block the matrix-free paths use (maximin sampling's
    frontier update, dvat's recomputed distance rows) — no (n, n) or even
    (n, m) intermediate.

    Args:
      X: (n, d) float — data points.
      x: (d,) float — the probe point.
      metric: one of ``METRICS``.

    Returns:
      (n,) float32 dissimilarities, matching ``pairwise_dissim_ref``'s
      column for the same point up to f32 rounding (this path computes
      differences directly instead of the Gram trick, which is the more
      accurate formula — do not mix the two inside one bitwise contract).
    """
    check_metric(metric)
    Xf = X.astype(jnp.float32)
    xf = x.astype(jnp.float32)
    diff = Xf - xf[None, :]
    if metric == "euclidean":
        return jnp.sqrt(jnp.maximum(jnp.sum(diff * diff, axis=-1), 0.0))
    if metric == "sqeuclidean":
        return jnp.sum(diff * diff, axis=-1)
    if metric == "manhattan":
        return jnp.sum(jnp.abs(diff), axis=-1)
    nx = jnp.sqrt(jnp.sum(Xf * Xf, axis=-1))
    nq = jnp.sqrt(jnp.sum(xf * xf))
    denom = jnp.maximum(nx * nq, 1e-12)
    return jnp.clip(1.0 - _dot(Xf, xf) / denom, 0.0, 2.0)


def metric_aux_ref(X: jax.Array, *, metric: str = "euclidean") -> jax.Array:
    """Per-point auxiliary vector the Gram-trick pivot row needs.

    Args:
      X: (n, d) float — data points (any leading batch axes are fine).
      metric: one of ``METRICS``.

    Returns:
      (n,) float32 — squared norms for euclidean/sqeuclidean, norms for
      cosine, zeros for manhattan (which needs no precomputation).
      Computed once, it turns every later pivot row into O(n d) work
      with no per-row norm recomputation.
    """
    check_metric(metric)
    Xf = X.astype(jnp.float32)
    if metric in ("euclidean", "sqeuclidean"):
        return jnp.sum(Xf * Xf, axis=-1)
    if metric == "cosine":
        return jnp.sqrt(jnp.sum(Xf * Xf, axis=-1))
    return jnp.zeros(Xf.shape[:-1], jnp.float32)


def pivot_row_ref(X: jax.Array, aux: jax.Array, q: jax.Array, *,
                  metric: str = "euclidean",
                  form: str = "gram") -> jax.Array:
    """Row q of the pairwise dissimilarity matrix, never materializing it.

    The matrix-free Prim engine's inner product: one (n, d) x (d,) cross
    term plus O(n) elementwise work per call.  This path uses the *same
    decomposition as* ``pairwise_dissim_ref`` for the same ``form``, so
    its values are bitwise-identical to the materialized matrix's row q
    — the property ``core.vat.vat_matrix_free`` needs to reproduce
    ``vat_order``'s ordering exactly.  Do not mix forms (or this oracle
    with ``row_dissim_ref``'s slightly different clamp) inside one
    bitwise contract.

    Args:
      X: (n, d) float — data points.
      aux: (n,) float32 — ``metric_aux_ref(X, metric=metric)``.
      q: int scalar (traced ok) — the pivot row index.
      metric: one of ``METRICS``.
      form: "gram" (default) or "direct" — see ``pairwise_dissim_ref``.

    Returns:
      (n,) float32 — dissimilarity of every point to point q.  The
      self-entry [q] is computed, not forced to zero; callers that need
      the materialized matrix's exact zero diagonal must mask it.
    """
    check_metric(metric)
    check_form(form)
    Xf = X.astype(jnp.float32)
    xq = jnp.take(Xf, q, axis=0)
    if metric == "manhattan":
        return jnp.sum(jnp.abs(Xf - xq[None, :]), axis=-1)
    if form == "direct" and metric != "cosine":
        diff = Xf - xq[None, :]
        sq = jnp.sum(diff * diff, axis=-1)
        return jnp.sqrt(sq) if metric == "euclidean" else sq
    cross = _dot(Xf, xq)
    aq = jnp.take(aux, q)
    if metric == "cosine":
        denom = jnp.maximum(aux * aq, 1e-12)
        return jnp.clip(1.0 - cross / denom, 0.0, 2.0)
    sq = jnp.maximum(aux + aq - 2.0 * cross, 0.0)
    return jnp.sqrt(sq) if metric == "euclidean" else sq


def pivot_row_from_point_ref(X: jax.Array, aux: jax.Array, xq: jax.Array,
                             auxq: jax.Array, *,
                             metric: str = "euclidean",
                             form: str = "gram") -> jax.Array:
    """``pivot_row_ref`` when the pivot's (point, aux) are already in hand.

    The building block of the sharded matrix-free engine: the pivot
    usually lives on another device, so its row x_q arrives by collective
    broadcast rather than a local gather.  The formula is *identical* to
    ``pivot_row_ref`` term for term (same decomposition per ``form``,
    same clamps), so a shard's slice of this row is bitwise-equal to the
    solo path's row restricted to the shard — the property the sharded
    ordering contract rests on.

    Args:
      X: (n, d) float — data points (a device's local shard is fine).
      aux: (n,) float32 — ``metric_aux_ref`` of X.
      xq: (d,) float — the pivot point.
      auxq: float32 scalar — the pivot's ``metric_aux_ref`` entry.
      metric: one of ``METRICS``.
      form: "gram" (default) or "direct" — see ``pairwise_dissim_ref``.

    Returns:
      (n,) float32 dissimilarity of every row of X to xq.
    """
    check_metric(metric)
    check_form(form)
    Xf = X.astype(jnp.float32)
    xqf = xq.astype(jnp.float32)
    if metric == "manhattan":
        return jnp.sum(jnp.abs(Xf - xqf[None, :]), axis=-1)
    if form == "direct" and metric != "cosine":
        diff = Xf - xqf[None, :]
        sq = jnp.sum(diff * diff, axis=-1)
        return jnp.sqrt(sq) if metric == "euclidean" else sq
    cross = _dot(Xf, xqf)
    if metric == "cosine":
        denom = jnp.maximum(aux * auxq, 1e-12)
        return jnp.clip(1.0 - cross / denom, 0.0, 2.0)
    sq = jnp.maximum(aux + auxq - 2.0 * cross, 0.0)
    return jnp.sqrt(sq) if metric == "euclidean" else sq


def prim_frontier_step_ref(X: jax.Array, aux: jax.Array, xq: jax.Array,
                           auxq: jax.Array, mind: jax.Array, *,
                           metric: str = "euclidean", form: str = "gram"):
    """Fused frontier fold + masked argmin with the pivot passed by value.

    The per-device body of ``core.distributed.vat_matrix_free_sharded``:
    fold the broadcast pivot's distance row into the local frontier and
    emit the local (min, argmin) pair for the cross-device reduction.

    Selected lanes are encoded *in-band* as ``mind = +inf`` (the
    persistent engine's convention — see ``prim_persist_ref``): the fold
    keeps +inf lanes +inf, so no separate ``selected`` mask ships through
    the loop.  Bitwise contract: folds are f32 ``min`` (exact, so fold
    order never matters) over rows identical to ``pivot_row_ref``.

    Args:
      X: (n, d) float — local points.
      aux: (n,) float32 — ``metric_aux_ref`` of X.
      xq: (d,) float — the pivot point (broadcast from its owner).
      auxq: f32 scalar — the pivot's aux entry.
      mind: (n,) float32 — frontier; +inf lanes are selected/padding.
      metric: one of ``METRICS``.
      form: "gram" (default) or "direct" — see ``pairwise_dissim_ref``.

    Returns:
      (new_mind (n,) f32, value f32 scalar, idx i32 scalar) — the updated
      frontier and its min with first-index tie-breaking.
    """
    row = pivot_row_from_point_ref(X, aux, xq, auxq, metric=metric,
                                   form=form)
    new_mind = jnp.where(jnp.isinf(mind), jnp.inf, jnp.minimum(mind, row))
    value = jnp.min(new_mind)
    n = new_mind.shape[0]
    idx = jnp.min(jnp.where(new_mind == value,
                            jnp.arange(n, dtype=jnp.int32), n)).astype(
                                jnp.int32)
    return new_mind, value, idx


#: "No distance folded yet" sentinel of the persistent engine's in-band
#: frontier encoding (+inf = selected).  Any real dissimilarity folds
#: below it; it can only win the argmin on pathological (inf/nan) input,
#: which no metric here produces from finite points.
UNSEEN = float(jnp.finfo(jnp.float32).max)


def prim_persist_ref(X: jax.Array, aux: jax.Array, i0: jax.Array, *,
                     metric: str = "euclidean", form: str = "gram",
                     unroll: int = 4):
    """The whole Prim traversal in one call — the persistent engine's
    XLA mirror (Turbo Flash-VAT).

    Where the stepwise path (``prim_stream_step_ref`` driven by
    ``core.vat``'s fori_loop) re-enters the runtime every step, this
    mirror keeps the entire n-1 step recurrence inside a single scan and
    strips the per-step op count to the bone:

      * selected lanes live *in-band* as ``mind = +inf`` (one carried
        vector instead of mind + selected + per-step masking),
      * the masked argmin is a vectorized ``min`` + index-min over
        ``where(mind == min, iota, n)`` — XLA:CPU lowers ``jnp.argmin``'s
        variadic reduce to a scalar loop, and replacing it is worth ~3x
        on the whole traversal at n = 8192,
      * order/edges are carried (n,) buffers updated in place by
        ``dynamic_update_slice`` — scan ys would need a concatenate for
        the seed slot, which blocks XLA's in-place ys accumulation and
        costs ~2x the whole loop,
      * the scan is unrolled to amortize loop bookkeeping.

    Bitwise contract with ``core.vat.vat_order`` / the stepwise engine:
    rows come from ``pivot_row_ref`` (the shared Gram-trick oracle), f32
    ``min`` folds are exact so fold scheduling can't change values, and
    the index-min reduction reproduces ``jnp.argmin``'s first-index
    tie-breaking (the winner set {mind == min} is exact equality on
    identical floats).

    Args:
      X: (n, d) float — data points.
      aux: (n,) float32 — ``metric_aux_ref`` of X.
      i0: i32 scalar — the seed vertex (``core.vat._streamed_seed_pivot``).
      metric: one of ``METRICS``.
      form: "gram" (default) or "direct" — see ``pairwise_dissim_ref``.
      unroll: scan unroll factor (static; perf only).

    Returns:
      (order (n,) i32, edges (n,) f32) — the exact VAT visit order and
      each visit's MST edge weight (edges[0] = 0), matching the stepwise
      engine bitwise.
    """
    check_metric(metric)
    n = X.shape[0]
    Xf = X.astype(jnp.float32)
    iota = jnp.arange(n, dtype=jnp.int32)
    q0 = jnp.asarray(i0, jnp.int32)
    mind0 = jnp.where(iota == q0, jnp.inf, jnp.float32(UNSEEN))
    order0 = jnp.zeros((n,), jnp.int32).at[0].set(q0)
    edges0 = jnp.zeros((n,), jnp.float32)
    if n == 1:
        return order0, edges0

    def step(carry, t):
        mind, q, order, edges = carry
        row = pivot_row_ref(Xf, aux, q, metric=metric, form=form)
        mind = jnp.where(jnp.isinf(mind), jnp.inf, jnp.minimum(mind, row))
        ev = jnp.min(mind)
        nq = jnp.min(jnp.where(mind == ev, iota, n)).astype(jnp.int32)
        mind = jax.lax.dynamic_update_slice(
            mind, jnp.reshape(ev * 0 + jnp.inf, (1,)), (nq,))
        order = jax.lax.dynamic_update_slice(order, nq[None], (t,))
        edges = jax.lax.dynamic_update_slice(edges, ev[None], (t,))
        return (mind, nq, order, edges), None

    (_, _, order, edges), _ = jax.lax.scan(
        step, (mind0, q0, order0, edges0), jnp.arange(1, n), unroll=unroll)
    return order, edges


def prim_stream_step_ref(X: jax.Array, aux: jax.Array, q: jax.Array,
                         mind: jax.Array, selected: jax.Array, *,
                         metric: str = "euclidean", form: str = "gram"):
    """One fused matrix-free Prim step — the XLA oracle for prim_stream.

    Recomputes pivot q's distance row, folds it into the frontier with a
    min-update, and returns the masked argmin over the *updated* frontier
    — the next vertex Prim visits.  Chaining n-1 of these reproduces
    ``core.vat.vat_order`` on the materialized matrix bitwise (the row
    values are bitwise-identical via ``pivot_row_ref``, and the argmin
    shares jnp.argmin's first-index tie-breaking).

    Args:
      X: (n, d) float — data points.
      aux: (n,) float32 — ``metric_aux_ref`` of X.
      q: int scalar — the pivot selected by the previous step.
      mind: (n,) float32 — frontier distances *before* folding in q's row.
      selected: (n,) bool — True lanes are already in the MST (q included).
      metric: one of ``METRICS``.
      form: "gram" (default) or "direct" — see ``pairwise_dissim_ref``.

    Returns:
      (new_mind (n,) f32, edge f32 scalar — the masked min (the MST edge
      weight of the next vertex), next (i32 scalar) — the next vertex).
    """
    row = pivot_row_ref(X, aux, q, metric=metric, form=form)
    new_mind = jnp.minimum(mind, row)
    edge, nxt = masked_argmin_ref(new_mind, selected)
    return new_mind, edge, nxt


def masked_argmin_ref(vals: jax.Array, mask: jax.Array):
    """(min value, argmin index) of vals where mask is False.

    Args:
      vals: (n,) float — candidate values (Prim frontier distances).
      mask: (n,) bool — True means "excluded" (already selected).

    Returns:
      (min value: f32 scalar, argmin index: i32 scalar) over unmasked
      lanes, first-index tie-breaking, matching jnp.argmin.
    """
    masked = jnp.where(mask, jnp.inf, vals.astype(jnp.float32))
    idx = jnp.argmin(masked).astype(jnp.int32)
    return masked[idx], idx


def ivat_from_vat_ref(rstar: jax.Array) -> jax.Array:
    """iVAT geodesic transform — the XLA fallback for kernels/ivat_update.

    Args:
      rstar: (n, n) float — VAT-ordered dissimilarity matrix.

    Returns:
      (n, n) float32 — max-min path distance matrix D' (Havens & Bezdek
      2012 recurrence; see ``core.ivat.ivat_from_vat`` for the math).

    Each fori_loop step is a fully vectorized O(n) row update, but the
    two ``at[].set`` writes lower to full-matrix dynamic_update_slice
    copies — the cost the fused Pallas kernel removes by keeping D'
    resident in VMEM.
    """
    n = rstar.shape[0]
    R = rstar.astype(jnp.float32)
    idx = jnp.arange(n)

    def body(r, Dp):
        row = R[r]
        mask = idx < r
        j = jnp.argmin(jnp.where(mask, row, jnp.inf))
        # D'[r,k] = max(R*[r,j], D'[j,k]) for k<r; at k=j, D'[j,j]=0 gives R*[r,j]
        newrow = jnp.where(mask, jnp.maximum(R[r, j], Dp[j]), 0.0)
        Dp = Dp.at[r, :].set(newrow)
        Dp = Dp.at[:, r].set(newrow)
        return Dp

    return jax.lax.fori_loop(1, n, body, jnp.zeros_like(R))
