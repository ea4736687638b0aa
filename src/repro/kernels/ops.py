"""Jit'd public wrappers for the kernels package.

Dispatch policy: Pallas kernels target TPU, where they compile through
Mosaic; on the CPU backend they run in ``interpret=True`` mode for
correctness validation, while the default production path on CPU is the
XLA reference in ref.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import faults
from repro.kernels import ref
from repro.kernels.ivat_update import MAX_FUSED_N, ivat_from_vat_pallas
from repro.kernels.knn_graph import (MAX_PALLAS_K, XLA_BLOCK,
                                     knn_graph_blocked, knn_graph_pallas,
                                     knn_graph_pallas_batch)
from repro.kernels.pairwise_dist import (pairwise_dist_pallas,
                                         pairwise_dist_pallas_batch)
from repro.kernels.prim_persist import (persist_supported,
                                        prim_persist_pallas)
from repro.kernels.prim_stream import (prim_frontier_step_pallas,
                                       prim_stream_step_pallas,
                                       prim_stream_step_pallas_batch)
from repro.kernels.prim_update import masked_argmin_pallas


def _interpret() -> bool:
    """Interpret mode on the CPU backend only; every other backend
    compiles the kernel (a refusal surfaces, it is never interpreted)."""
    return jax.default_backend() == "cpu"


def _dispatch_site(op: str, use_pallas: bool) -> None:
    """The ``kernels.dispatch`` fault-injection site (ISSUE 9).

    Called at the top of every public wrapper — i.e. at *trace* time
    when the wrapper runs under jit — so an armed fault here models a
    kernel compile/build failure: fresh traces raise, already-compiled
    programs are untouched.  Disarmed (production) this is one dict
    truthiness check; it adds nothing to the jaxpr, so the dispatch
    census stays byte-identical (pinned by tests/test_resilience.py).
    """
    faults.fault_point("kernels.dispatch",
                       context={"op": op, "use_pallas": use_pallas})


def pairwise_dist(X: jax.Array, Y: jax.Array | None = None, *,
                  metric: str = "euclidean", form: str = "gram",
                  use_pallas: bool = False, block: int = 256) -> jax.Array:
    """Pairwise dissimilarity matrix; Pallas-tiled on request, XLA otherwise.

    Args:
      X: (n, d) float — query points.
      Y: (m, d) float or None — reference points; None means self-
        dissimilarities (and forces an exactly-zero diagonal).
      metric: one of ``kernels.ref.METRICS`` (euclidean | sqeuclidean |
        manhattan | cosine). "precomputed" is an API-layer concept and
        never reaches the kernels.
      form: "gram" (default) or "direct" — the numerics-policy tile
        form, resolved host-side by ``numerics.resolve`` (static).
      use_pallas: route through the MXU-tiled Pallas kernel (interpret
        mode on CPU; compiled on TPU). Default is the XLA reference path.
      block: Pallas output tile edge.

    Returns:
      (n, m) float32 dissimilarity matrix ((n, n) when Y is None).
    """
    _dispatch_site("pairwise_dist", use_pallas)
    if use_pallas:
        R = pairwise_dist_pallas(X, Y, metric=metric, form=form, block=block,
                                 interpret=_interpret())
    else:
        R = ref.pairwise_dissim_ref(X, Y, metric=metric, form=form)
    if Y is None:  # exact zero diagonal for self-dissimilarities
        n = R.shape[0]
        R = R * (1.0 - jnp.eye(n, dtype=R.dtype))
    return R


def pairwise_dist_batch(X: jax.Array, *, metric: str = "euclidean",
                        form: str = "gram", use_pallas: bool = False,
                        block: int = 256) -> jax.Array:
    """Per-dataset self-dissimilarity matrices for a (b, n, d) stack.

    Args:
      X: (b, n, d) float — b independent datasets.
      metric: one of ``kernels.ref.METRICS``.
      form: "gram" (default) or "direct" — the numerics-policy tile form.
      use_pallas: route through the batched-grid Pallas kernel
        (``pairwise_dist_pallas_batch``); default is a vmap of the XLA
        reference, which lowers to one batched dot_general.
      block: Pallas output tile edge.

    Returns:
      (b, n, n) float32 stack with exactly-zero diagonals.
    """
    _dispatch_site("pairwise_dist_batch", use_pallas)
    if use_pallas:
        R = pairwise_dist_pallas_batch(X, metric=metric, form=form,
                                       block=block, interpret=_interpret())
    else:
        R = jax.vmap(
            lambda A: ref.pairwise_dissim_ref(A, metric=metric,
                                              form=form))(X)
    n = R.shape[-1]
    return R * (1.0 - jnp.eye(n, dtype=R.dtype))


def knn_graph(X: jax.Array, *, k: int, metric: str = "euclidean",
              use_pallas: bool = False, block: int | None = None):
    """k-nearest-neighbour graph at O(n·k) memory; never builds (n, n).

    The approximate-MST rung's first stage.  Both paths share one tie
    contract (lower index wins on equal distances) and one output shape;
    see ``kernels/knn_graph.py`` for the tiling story.

    Args:
      X: (n, d) float — data points.
      k: neighbours per point (1 <= k <= n-1).
      metric: one of ``kernels.ref.METRICS``.
      use_pallas: route through the fused Pallas top-k fold (interpret
        mode on CPU; compiled on TPU).  k > ``MAX_PALLAS_K`` exceeds the
        fold's unroll budget and silently takes the XLA driver instead
        (the ``MAX_FUSED_N`` precedent).
      block: tile edge; None picks each path's default (the Pallas tile
        is VMEM-bound, the XLA tile dispatch-bound, so they differ).

    Returns:
      (dist (n, k) f32 ascending per row, idx (n, k) i32) — idx[i, 0] is
      i's nearest neighbour; a point is never its own neighbour.
    """
    _dispatch_site("knn_graph", use_pallas)
    if use_pallas and k <= MAX_PALLAS_K:
        return knn_graph_pallas(X, k=k, metric=metric,
                                block=block if block is not None else 256,
                                interpret=_interpret())
    return knn_graph_blocked(
        X, k=k, metric=metric,
        block=block if block is not None else XLA_BLOCK)


def knn_graph_batch(X: jax.Array, *, k: int, metric: str = "euclidean",
                    use_pallas: bool = False, block: int | None = None):
    """Per-dataset kNN graphs for a (b, n, d) stack.

    Args:
      X: (b, n, d) float — b independent datasets.
      k, metric, use_pallas, block: as ``knn_graph``; the Pallas path is
        the slab-of-1 batched grid, the XLA path a vmap of the blocked
        driver.

    Returns:
      (dist (b, n, k) f32, idx (b, n, k) i32).
    """
    _dispatch_site("knn_graph_batch", use_pallas)
    if use_pallas and k <= MAX_PALLAS_K:
        return knn_graph_pallas_batch(
            X, k=k, metric=metric,
            block=block if block is not None else 256,
            interpret=_interpret())
    return jax.vmap(lambda A: knn_graph_blocked(
        A, k=k, metric=metric,
        block=block if block is not None else XLA_BLOCK))(X)


def masked_argmin(vals: jax.Array, mask: jax.Array, *,
                  use_pallas: bool = False, block: int = 1024):
    """(min, argmin) over unmasked entries (mask=True excludes).

    Args:
      vals: (n,) float — candidate values.
      mask: (n,) bool — True lanes are excluded from the reduction.
      use_pallas: fused block-argmin kernel vs the XLA reference.
      block: Pallas VMEM tile length.

    Returns:
      (f32 scalar min, i32 scalar argmin), first-index tie-breaking.
    """
    _dispatch_site("masked_argmin", use_pallas)
    if use_pallas:
        return masked_argmin_pallas(vals, mask, block=block,
                                    interpret=_interpret())
    return ref.masked_argmin_ref(vals, mask)


def prim_stream_step(X: jax.Array, aux: jax.Array, q: jax.Array,
                     mind: jax.Array, selected: jax.Array, *,
                     metric: str = "euclidean", form: str = "gram",
                     use_pallas: bool = False, block: int = 1024):
    """One fused matrix-free Prim step (the Flash-VAT hot loop).

    Recomputes pivot q's distance row tile-by-tile, folds it into the
    frontier min-update, and returns the masked argmin over the updated
    frontier — the next Prim vertex — without ever forming the (n, n)
    matrix.  Solo (n,)-state and batched (b, n)-state inputs both work:
    the batched Pallas path uses the slab-of-1 grid, the batched XLA
    path a vmap of the reference step.

    Args:
      X: (n, d) or (b, n, d) float — data points.  The Pallas path wants
        these pre-padded by ``kernels.prim_stream.pad_points`` (padding
        per step would copy X n times); the XLA path is pad-agnostic.
      aux: (n,) or (b, n) float32 — ``ref.metric_aux_ref`` of X.
      q: i32 scalar or (b,) — pivot(s) selected by the previous step.
      mind: like aux — frontier distances (padded lanes +inf).
      selected: bool, like aux — visited mask (padded lanes True).
      metric: one of ``kernels.ref.METRICS``.
      form: "gram" (default) or "direct" — the numerics-policy tile form.
      use_pallas: fused Pallas kernel vs the XLA reference step.
      block: Pallas VMEM tile length (must divide the padded n).

    Returns:
      (new_mind, edge, next) with the input's leading shape — see
      ``ref.prim_stream_step_ref``.
    """
    _dispatch_site("prim_stream_step", use_pallas)
    batched = X.ndim == 3
    if use_pallas:
        step = (prim_stream_step_pallas_batch if batched
                else prim_stream_step_pallas)
        return step(X, aux, q, mind, selected, metric=metric, form=form,
                    block=block, interpret=_interpret())
    if batched:
        return jax.vmap(
            lambda Xi, ai, qi, mi, si: ref.prim_stream_step_ref(
                Xi, ai, qi, mi, si, metric=metric, form=form)
        )(X, aux, q, mind, selected)
    return ref.prim_stream_step_ref(X, aux, q, mind, selected, metric=metric,
                                    form=form)


def prim_persist(X: jax.Array, aux: jax.Array, i0: jax.Array, *,
                 metric: str = "euclidean", form: str = "gram",
                 block: int = 1024, use_pallas: bool = False):
    """The whole Prim traversal in one dispatch (the Turbo engine).

    Solo (n, d) input runs the persistent path: the Pallas megakernel
    (``kernels/prim_persist.py`` — one pallas_call, VMEM-resident state,
    lazy-Prim tile pruning) when requested AND its resident state fits
    ``PERSIST_VMEM_BUDGET``, else the single-scan XLA mirror
    (``ref.prim_persist_ref``).  The fallback is always the *persistent*
    mirror — the stepwise engine is never silently substituted (pinned
    by tests/test_turbo.py).  Batched (b, n, d) input vmaps the mirror:
    the megakernel is deliberately solo-only (its DMA streaming does not
    batch), so ``core.vat_matrix_free_batch`` maps the solo engine over
    the lanes on the Pallas path instead of calling this batched.

    Args:
      X: (n, d) or (b, n, d) float — data points (unpadded).
      aux: (n,) or (b, n) float32 — ``ref.metric_aux_ref`` of X.
      i0: i32 scalar or (b,) — seed vertex per dataset.
      metric: one of ``kernels.ref.METRICS``.
      form: "gram" (default) or "direct" — the numerics-policy tile
        form; the megakernel's pruning slack is debited per form.
      block: megakernel X-tile length.
      use_pallas: megakernel vs the XLA mirror (solo only).

    Returns:
      (order, edges) with the input's leading shape — (n,)/(b, n) i32
      and f32.  Every path runs the same exact Prim recurrence, so the
      spanning-tree weight (the edge sum) agrees across paths to f32
      rounding.  The orders agree except at near-ties, which the
      megakernel's and the mirror's dot lowerings may break differently
      (on a TPU v5e at n = 50 000 the two orders part in about 2.5% of
      positions; on the CPU the pinned cases are bitwise-equal).
    """
    _dispatch_site("prim_persist", use_pallas)
    if X.ndim == 3:
        return jax.vmap(lambda Xi, ai, ii: ref.prim_persist_ref(
            Xi, ai, ii, metric=metric, form=form))(X, aux, i0)
    if use_pallas and persist_supported(X.shape[0], X.shape[1], block=block):
        order, edges, _ = prim_persist_pallas(X, aux, i0, metric=metric,
                                              form=form, block=block,
                                              interpret=_interpret())
        return order, edges
    return ref.prim_persist_ref(X, aux, i0, metric=metric, form=form)


def prim_frontier_step(X: jax.Array, aux: jax.Array, xq: jax.Array,
                       auxq: jax.Array, mind: jax.Array, *,
                       metric: str = "euclidean", form: str = "gram",
                       use_pallas: bool = False, block: int = 1024):
    """Fused frontier fold + masked argmin, pivot passed by value.

    The per-device body of the sharded matrix-free engine
    (``core.distributed.vat_matrix_free_sharded``): the pivot row arrives
    by collective broadcast, the device folds it into its local frontier
    and emits the local (min, argmin) pair for the cross-device
    reduction.  Selected/padded lanes are carried *in-band* as
    ``mind = +inf`` (see ``ref.prim_frontier_step_ref``); the Pallas
    path derives its mask from that and re-masks the folded frontier so
    the in-band encoding survives the kernel.

    Args:
      X: (n, d) float — local points (Pallas path: pre-padded, with
        ``block`` dividing n).
      aux: (n,) float32 — ``ref.metric_aux_ref`` of X.
      xq: (d,) float — the pivot point.
      auxq: f32 scalar — the pivot's aux entry.
      mind: (n,) float32 — in-band frontier (+inf = selected/padding).
      metric: one of ``kernels.ref.METRICS``.
      form: "gram" (default) or "direct" — the numerics-policy tile form.
      use_pallas: fused Pallas tile kernel vs the XLA reference.
      block: Pallas VMEM tile length.

    Returns:
      (new_mind (n,) f32, value f32 scalar, idx i32 scalar) — first-index
      tie-breaking, identical across both paths.
    """
    _dispatch_site("prim_frontier_step", use_pallas)
    if use_pallas:
        selected = jnp.isinf(mind)
        new_mind, value, idx = prim_frontier_step_pallas(
            X, aux, xq, auxq, mind, selected, metric=metric, form=form,
            block=block, interpret=_interpret())
        return jnp.where(selected, jnp.inf, new_mind), value, idx
    return ref.prim_frontier_step_ref(X, aux, xq, auxq, mind, metric=metric,
                                      form=form)


def kernel_dispatch_stats(fn, *args, **kwargs) -> dict:
    """Static dispatch census of a jittable function: how many
    ``pallas_call`` equations its jaxpr holds, and how many sit OUTSIDE
    any loop (while/scan) — i.e. run exactly once per invocation.

    The persistent-engine regression gate reads this: the Turbo path
    must show one loop-free pallas_call (the megakernel), while the
    stepwise engine's kernel lives under the Prim while-loop and
    re-dispatches every step.

    Args:
      fn: the function to trace (positional ``args`` / keyword
        ``kwargs`` forwarded to ``jax.make_jaxpr``).

    Returns:
      {"pallas_calls": total count, "persistent": count outside loops}.
    """
    jaxpr = jax.make_jaxpr(functools.partial(fn, **kwargs))(*args).jaxpr

    def walk(jx, in_loop):
        total = persistent = 0
        for eqn in jx.eqns:
            name = eqn.primitive.name
            if name == "pallas_call":
                total += 1
                persistent += 0 if in_loop else 1
            looped = in_loop or name in ("while", "scan")
            for v in eqn.params.values():
                for u in (v if isinstance(v, (list, tuple)) else (v,)):
                    sub = getattr(u, "jaxpr", u)
                    if hasattr(sub, "eqns"):
                        t, p = walk(sub, looped)
                        total += t
                        persistent += p
        return total, persistent

    total, persistent = walk(jaxpr, False)
    return {"pallas_calls": total, "persistent": persistent}


def ivat_from_vat(rstar: jax.Array, *, use_pallas: bool = False) -> jax.Array:
    """iVAT geodesic transform of VAT-ordered dissimilarities.

    Args:
      rstar: (n, n) or (b, n, n) float — VAT-ordered matrix/stack.
      use_pallas: route through the fused VMEM-resident row-update kernel
        (``kernels/ivat_update.py``; interpret mode on CPU, compiled on
        TPU). Matrices with n > ``MAX_FUSED_N`` exceed the kernel's VMEM
        slab budget and silently take the XLA fallback instead.

    Returns:
      (n, n) or (b, n, n) float32 max-min path distance matrix/stack.
    """
    _dispatch_site("ivat_from_vat", use_pallas)
    n = rstar.shape[-1]
    if use_pallas and n <= MAX_FUSED_N:
        return ivat_from_vat_pallas(rstar, interpret=_interpret())
    if rstar.ndim == 3:
        return jax.vmap(ref.ivat_from_vat_ref)(rstar)
    return ref.ivat_from_vat_ref(rstar)
