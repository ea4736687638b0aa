"""VAT — Visual Assessment of Cluster Tendency, JAX-native.

The paper accelerates three stages; each has a TPU-native counterpart here:

  1. pairwise dissimilarity  -> kernels/pairwise_dist (MXU-tiled Pallas) or
                                the XLA path in kernels/ref.py
  2. Prim MST reordering     -> ``vat_order``: lax.fori_loop with a fully
                                vectorized O(n) min-update + argmin step
  3. matrix reordering       -> one gather, ``reorder``

``vat_matrix_free`` is the Flash-VAT engine: the same exact ordering
without ever materializing the (n, n) matrix — distance rows are
recomputed tile-by-tile and reduced on the fly, so exact VAT runs at
O(n·d) memory and n = 10^5 fits a laptop CPU.  Two traversal engines
share that contract: the default Turbo persistent engine (ISSUE 5 —
the whole recurrence in ONE dispatch, ``kernels/prim_persist.py``
megakernel or its single-scan XLA mirror) and the PR-4 stepwise engine
(``turbo=False``, n−1 fused ``kernels/prim_stream.py`` steps); the
mesh-sharded variant lives in ``core.distributed``.

All functions are jit-able and differentiable-safe (no Python side effects).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.kernels.prim_stream import pad_points


class VATResult(NamedTuple):
    rstar: jax.Array   # (n, n) reordered dissimilarity matrix
    order: jax.Array   # (n,) int32 permutation
    dist: jax.Array    # (n, n) original dissimilarity matrix


class FlashVATResult(NamedTuple):
    order: jax.Array   # (n,) int32 permutation — exact, same as vat_order
    edges: jax.Array   # (n,) float32 MST edge weight of each visit; [0]=0


def vat_order(R: jax.Array, *, use_pallas_argmin: bool = False) -> jax.Array:
    """Prim-based VAT ordering of a dissimilarity matrix.

    Args:
      R: (n, n) float — symmetric dissimilarity matrix, zero diagonal.
      use_pallas_argmin: route the per-step masked argmin through the
        fused ``prim_update`` Pallas kernel (the Numba-accelerated hot
        loop of the paper); on CPU it runs in interpret mode — TPU is the
        target.

    Returns:
      (n,) int32 permutation — the VAT visit order.

    Matches ``core.naive.vat_order_naive`` exactly (first vertex = row of
    the global max; greedy min-edge growth; first-index tie-breaking, which
    jnp.argmin / the naive `<` scan share).
    """
    n = R.shape[0]
    i0 = jnp.argmax(jnp.max(R, axis=1)).astype(jnp.int32)
    order0 = jnp.zeros((n,), jnp.int32).at[0].set(i0)
    selected0 = jnp.zeros((n,), jnp.bool_).at[i0].set(True)
    mind0 = R[i0]

    def body(t, carry):
        mind, selected, order = carry
        if use_pallas_argmin:
            _, q = kops.masked_argmin(mind, selected, use_pallas=True)
        else:
            q = jnp.argmin(jnp.where(selected, jnp.inf, mind)).astype(jnp.int32)
        order = order.at[t].set(q)
        selected = selected.at[q].set(True)
        mind = jnp.minimum(mind, R[q])
        return mind, selected, order

    _, _, order = lax.fori_loop(1, n, body, (mind0, selected0, order0))
    return order


def reorder(R: jax.Array, order: jax.Array) -> jax.Array:
    """R* = R[order][:, order] — one gather along each axis.

    Args:
      R: (n, n) float — dissimilarity matrix.
      order: (n,) int — permutation from ``vat_order``.

    Returns:
      (n, n) float — R with rows and columns permuted by ``order``.
    """
    return R[order][:, order]


@functools.partial(jax.jit, static_argnames=("use_pallas", "metric", "form"))
def vat(X: jax.Array, *, use_pallas: bool = False,
        metric: str = "euclidean", form: str = "gram") -> VATResult:
    """Full VAT on a data matrix.

    Args:
      X: (n, d) float — data points.
      use_pallas: route BOTH hot paths through Pallas kernels — the
        dissimilarity matrix (``kernels/pairwise_dist``) and the per-step
        masked argmin of the Prim loop (``kernels/prim_update``).
        Interpret mode on CPU; compiled on TPU.  Default is XLA.
      metric: dissimilarity metric, one of ``kernels.ref.METRICS``.
        For an already-computed matrix use ``vat_from_dist`` instead.
      form: "gram" (default) or "direct" — the numerics-policy tile
        form (static; resolved host-side by ``numerics.resolve``).

    Returns:
      VATResult — rstar (n, n) reordered image, order (n,) int32
      permutation, dist (n, n) original dissimilarities.
    """
    R = kops.pairwise_dist(X, use_pallas=use_pallas, metric=metric,
                           form=form)
    order = vat_order(R, use_pallas_argmin=use_pallas)
    return VATResult(rstar=reorder(R, order), order=order, dist=R)


@functools.partial(jax.jit, static_argnames=("use_pallas_argmin",))
def vat_from_dist(R: jax.Array, *,
                  use_pallas_argmin: bool = False) -> VATResult:
    """VAT when the dissimilarity matrix is precomputed (paper step 2+3).

    Args:
      R: (n, n) float — symmetric dissimilarity matrix, zero diagonal.
      use_pallas_argmin: route the Prim loop's masked argmin through the
        fused ``prim_update`` Pallas kernel (see ``vat_order``).

    Returns:
      VATResult with ``dist`` aliasing the input R.
    """
    order = vat_order(R, use_pallas_argmin=use_pallas_argmin)
    return VATResult(rstar=reorder(R, order), order=order, dist=R)


@functools.partial(jax.jit, static_argnames=("use_pallas", "metric", "form"))
def vat_batch(X: jax.Array, *, use_pallas: bool = False,
              metric: str = "euclidean", form: str = "gram") -> VATResult:
    """Batched VAT: assess a stack of datasets in one compiled program.

    Args:
      X: (b, n, d) float — b independent datasets of n points each.
      use_pallas: route distances through the batched-grid Pallas kernel
        (``kernels.pairwise_dist_pallas_batch``) AND the Prim loop's
        masked argmin through the vmapped ``prim_update`` kernel;
        default is the batched XLA path.
      metric: dissimilarity metric, one of ``kernels.ref.METRICS``.
        For precomputed (b, n, n) stacks use ``vat_batch_from_dist``.
      form: "gram" (default) or "direct" — the numerics-policy tile form.

    Returns:
      VATResult whose fields carry a leading batch axis: rstar (b, n, n),
      order (b, n) int32, dist (b, n, n).

    The per-dataset ordering is bitwise-identical to ``vat`` on the same
    rows (the vmapped ``vat_order`` runs the same argmin/min-update steps
    per batch lane; no cross-dataset reduction exists anywhere).
    """
    R = kops.pairwise_dist_batch(X, use_pallas=use_pallas, metric=metric,
                                 form=form)
    return jax.vmap(
        lambda Ri: vat_from_dist(Ri, use_pallas_argmin=use_pallas))(R)


@functools.partial(jax.jit, static_argnames=("use_pallas_argmin",))
def vat_batch_from_dist(R: jax.Array, *,
                        use_pallas_argmin: bool = False) -> VATResult:
    """Batched ``vat_from_dist``: (b, n, n) stack -> batched VATResult."""
    return jax.vmap(
        lambda Ri: vat_from_dist(Ri, use_pallas_argmin=use_pallas_argmin)
    )(R)


# ------------------------------------------------------------------------
# Flash-VAT: matrix-free fused Prim ordering — exact VAT at O(n·d) memory.
# ------------------------------------------------------------------------

def _streamed_seed_pivot(Xf: jax.Array, *, metric: str, form: str = "gram",
                         use_pallas: bool = False) -> jax.Array:
    """VAT's seed vertex i0 = argmax_i max_j R[i, j], streamed.

    Reproduces ``vat_order``'s seed bitwise without forming R: (bs, bs)
    blocks of the matrix are recomputed through the one pairwise front
    door — ``kernels.ops.pairwise_dist``, so ``use_pallas`` reaches the
    MXU tile here exactly like everywhere else — and reduced to per-row
    maxima on the spot.  Every per-entry value depends only on its own
    (x_i, y_j) pair and f32 ``max`` is exact, so any blocking yields the
    same row maxima bit for bit.

    Blocks are square and sized to keep each in-flight tile near 4 MiB
    (times d for manhattan's broadcast form, which shrinks the block):
    cache-resident tiles let XLA's fused epilogue (diag mask + rowmax)
    read the matmul output before it spills, ~2.5x over the previous
    (br, n) strip mining at n = 8192.
    """
    n, d = Xf.shape
    broadcast = metric == "manhattan" or (form == "direct"
                                          and metric != "cosine")
    per_entry = 4 * (d if broadcast else 1)  # |diff|/(diff)^2 keep (bs,bs,d)
    bs = max(8, min(1024, int(((4 << 20) // per_entry) ** 0.5), n))
    n_pad = -(-n // bs) * bs
    Xp = jnp.pad(Xf, ((0, n_pad - n), (0, 0)))
    nblk = n_pad // bs
    lane = jnp.arange(bs)

    def row_block(i, acc):
        xb = lax.dynamic_slice_in_dim(Xp, i * bs, bs, 0)
        rids = i * bs + lane

        def col_block(j, rm):
            yb = lax.dynamic_slice_in_dim(Xp, j * bs, bs, 0)
            T = kops.pairwise_dist(xb, yb, metric=metric, form=form,
                                   use_pallas=use_pallas)
            cids = j * bs + lane
            T = jnp.where(cids[None, :] == rids[:, None], 0.0, T)  # diag
            T = jnp.where(cids[None, :] < n, T, -jnp.inf)          # padding
            return jnp.maximum(rm, jnp.max(T, axis=1))

        rm = lax.fori_loop(0, nblk, col_block, jnp.full((bs,), -jnp.inf))
        return lax.dynamic_update_slice_in_dim(acc, rm, i * bs, 0)

    rowmax = lax.fori_loop(0, nblk, row_block,
                           jnp.zeros((n_pad,), jnp.float32))
    return jnp.argmax(rowmax[:n]).astype(jnp.int32)


def _prim_stream_order(Xs, auxs, i0, n, *, metric, form, use_pallas, block):
    """Drive n-1 fused Prim steps from seed i0; shared by both paths.

    Args:
      Xs / auxs: points + metric auxiliary — pre-padded (Pallas path) or
        raw (XLA path); the step dispatch in ``kernels.ops`` is
        pad-agnostic because padded lanes arrive masked.
      i0: i32 scalar seed vertex.
      n: true (unpadded) point count — sizes the order/edges outputs.
    """
    m = Xs.shape[0]
    mind0 = jnp.full((m,), jnp.inf, jnp.float32)
    sel0 = (jnp.arange(m) >= n).at[i0].set(True)
    order0 = jnp.zeros((n,), jnp.int32).at[0].set(i0)
    edges0 = jnp.zeros((n,), jnp.float32)

    def body(t, carry):
        mind, sel, order, edges, q = carry
        mind, ev, nq = kops.prim_stream_step(
            Xs, auxs, q, mind, sel, metric=metric, form=form,
            use_pallas=use_pallas, block=block)
        return (mind, sel.at[nq].set(True), order.at[t].set(nq),
                edges.at[t].set(ev), nq)

    _, _, order, edges, _ = lax.fori_loop(
        1, n, body, (mind0, sel0, order0, edges0, i0))
    return FlashVATResult(order=order, edges=edges)


@functools.partial(jax.jit, static_argnames=("metric", "form", "block",
                                             "use_pallas", "turbo"))
def vat_matrix_free(X: jax.Array, *, metric: str = "euclidean",
                    form: str = "gram", block: int = 1024,
                    use_pallas: bool = False,
                    turbo: bool = True) -> FlashVATResult:
    """Exact VAT ordering of X without ever materializing the (n, n) matrix.

    The Flash-VAT engine: the seed pivot comes from a streamed row-max
    pass, then the Prim traversal runs through one of two engines:

      * ``turbo=True`` (default) — the persistent Turbo engine
        (``kernels.ops.prim_persist``): the entire n-1 step recurrence
        in ONE dispatch — the Pallas megakernel with VMEM-resident state
        and lazy-Prim tile pruning on the ``use_pallas`` path, the
        single-scan XLA mirror otherwise.  ~4x the stepwise engine at
        n = 8192 on CPU (benchmarks "turbo" table).
      * ``turbo=False`` — the PR-4 stepwise engine: n-1 fused steps
        (``kernels/prim_stream.py`` on the Pallas path, the vectorized
        XLA step otherwise), each re-entering the runtime.

    Peak memory is O(n·d) for X plus O(n) frontier state either way —
    never O(n^2) — so exact VAT scales to n = 10^5+ on a CPU and far
    beyond on accelerators.

    The ordering is bitwise-identical to ``vat_order`` on the
    materialized ``kernels.ops.pairwise_dist`` matrix for every metric
    and both engines: identical Gram-trick rows (``kernels.ref.
    pivot_row_ref``), exact f32 min folds, identical first-index
    tie-breaking, identical seed rule.

    Args:
      X: (n, d) float — data points.
      metric: dissimilarity metric, one of ``kernels.ref.METRICS``
        ("precomputed" is meaningless here — the point is to never hold
        the matrix; use ``vat_from_dist`` if you already have it).
      form: "gram" (default) or "direct" — the numerics-policy tile
        form, shared by the seed scan and the traversal (static).
      block: X-tile length of the fused kernels (static).
      use_pallas: route the traversal (and the seed scan's pairwise
        tiles) through the Pallas kernels (interpret mode on CPU;
        compiled on TPU).  Default is XLA — the production CPU path.
      turbo: persistent engine (True, default) vs stepwise (False).

    Returns:
      FlashVATResult — ``order`` (n,) int32 exact VAT visit order and
      ``edges`` (n,) float32, the MST edge weight that admitted each
      vertex (edges[0] = 0; large edges mark cluster boundaries, which
      is what ``block_structure_score`` reads off a VAT image's
      super-diagonal).
    """
    n = X.shape[0]
    Xf = X.astype(jnp.float32)
    aux = kref.metric_aux_ref(Xf, metric=metric)
    i0 = _streamed_seed_pivot(Xf, metric=metric, form=form,
                              use_pallas=use_pallas)
    if turbo:
        order, edges = kops.prim_persist(Xf, aux, i0, metric=metric,
                                         form=form, block=block,
                                         use_pallas=use_pallas)
        return FlashVATResult(order=order, edges=edges)
    if use_pallas:
        Xs, auxs, _, bn = pad_points(Xf, aux, block=block)
    else:
        Xs, auxs, bn = Xf, aux, block
    return _prim_stream_order(Xs, auxs, i0, n, metric=metric, form=form,
                              use_pallas=use_pallas, block=bn)


@functools.partial(jax.jit, static_argnames=("metric", "form", "block",
                                             "use_pallas", "turbo"))
def vat_matrix_free_batch(X: jax.Array, *, metric: str = "euclidean",
                          form: str = "gram", block: int = 1024,
                          use_pallas: bool = False,
                          turbo: bool = True) -> FlashVATResult:
    """Batched Flash-VAT: exact matrix-free orderings for a (b, n, d) stack.

    One compiled program serves all b datasets.  ``turbo=True`` (default)
    vmaps the persistent single-scan mirror on the XLA path; on the
    Pallas path the megakernel is solo-only (its DMA streaming does not
    batch), so the lanes run the solo engine one after another
    (``lax.map``) — the same kernel as a solo fit, hence the same order
    on a TPU, where the megakernel's and the mirror's dots round
    differently and near-ties can break apart; ``turbo=False`` keeps
    the stepwise engines — the XLA path vmaps the solo engine, the
    Pallas path drives the batched fused kernel (slab-of-1 grid,
    ``kernels.prim_stream.prim_stream_step_pallas_batch``) so
    per-program VMEM stays at the unbatched budget.  Each lane's
    ordering is bitwise-identical to ``vat_matrix_free`` on that dataset
    under every engine combination.

    Args:
      X: (b, n, d) float — b independent datasets.
      metric / form / block / use_pallas / turbo: as in
        ``vat_matrix_free``.

    Returns:
      FlashVATResult with a leading batch axis: order (b, n) int32,
      edges (b, n) float32.
    """
    if turbo and use_pallas:
        return lax.map(functools.partial(
            vat_matrix_free, metric=metric, form=form, block=block,
            use_pallas=True), X)
    if turbo:
        Xf = X.astype(jnp.float32)
        aux = kref.metric_aux_ref(Xf, metric=metric)
        i0 = jax.vmap(functools.partial(
            _streamed_seed_pivot, metric=metric, form=form))(Xf)
        order, edges = kops.prim_persist(Xf, aux, i0, metric=metric,
                                         form=form, block=block)
        return FlashVATResult(order=order, edges=edges)
    if not use_pallas:
        return jax.vmap(functools.partial(
            vat_matrix_free, metric=metric, form=form, block=block,
            turbo=False))(X)
    b, n, _ = X.shape
    Xf = X.astype(jnp.float32)
    aux = kref.metric_aux_ref(Xf, metric=metric)
    i0 = jax.vmap(functools.partial(
        _streamed_seed_pivot, metric=metric, form=form, use_pallas=True))(Xf)
    Xp, auxp, n_pad, bn = pad_points(Xf, aux, block=block)
    lane = jnp.arange(b)

    mind0 = jnp.full((b, n_pad), jnp.inf, jnp.float32)
    sel0 = jnp.broadcast_to(jnp.arange(n_pad) >= n, (b, n_pad))
    sel0 = sel0.at[lane, i0].set(True)
    order0 = jnp.zeros((b, n), jnp.int32).at[:, 0].set(i0)
    edges0 = jnp.zeros((b, n), jnp.float32)

    def body(t, carry):
        mind, sel, order, edges, q = carry
        mind, ev, nq = kops.prim_stream_step(
            Xp, auxp, q, mind, sel, metric=metric, form=form,
            use_pallas=True, block=bn)
        return (mind, sel.at[lane, nq].set(True),
                order.at[:, t].set(nq), edges.at[:, t].set(ev), nq)

    _, _, order, edges, _ = lax.fori_loop(
        1, n, body, (mind0, sel0, order0, edges0, i0))
    return FlashVATResult(order=order, edges=edges)


def block_structure_score(rstar: jax.Array, threshold: float | None = None):
    """Quantify diagonal block structure of a VAT image.

    Args:
      rstar: (n, n) float — VAT-reordered dissimilarity matrix.
      threshold: cut threshold as a fraction of the matrix mean; None
        derives one from the super-diagonal statistics (mean + 2 std,
        floored at half the largest jump).

    Returns:
      (score, k_est): `score` in [0, 1] — mean off-diagonal-band
      contrast; `k_est` — estimated number of diagonal blocks by counting
      super-diagonal "cuts" (adjacent-in-order distances above threshold).
      Used by diagnostics and by benchmarks/table3 to turn a VAT image
      into a machine-checkable "VAT insight".
    """
    n = rstar.shape[0]
    sup = jnp.diagonal(rstar, offset=1)           # adjacent-in-order dists
    scale = jnp.mean(rstar) + 1e-12
    if threshold is None:
        # a "cut" must stand out both locally (vs typical adjacent dist)
        # and globally (a sizeable fraction of the largest jump)
        thr = jnp.maximum(jnp.mean(sup) + 2.0 * jnp.std(sup),
                          0.5 * jnp.max(sup))
    else:
        thr = jnp.asarray(threshold) * scale
    cuts = jnp.sum(sup > thr)
    k_est = cuts + 1
    # contrast: how much darker the near-diagonal band is vs global mean
    band = jnp.mean(sup)
    score = jnp.clip(1.0 - band / scale, 0.0, 1.0)
    return score, k_est
