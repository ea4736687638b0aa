"""Pallas TPU kernel: fused matrix-free Prim step (the Flash-VAT engine).

Exact VAT needs, per Prim step, three things the materialized path reads
off the (n, n) matrix: the pivot's distance row, the frontier min-update
``mind = min(mind, row)``, and the masked argmin that picks the next
vertex.  This kernel does all three in ONE pass over X tiled from HBM —
FlashAttention's trick applied to Prim's traversal: recompute the
distance tile on the fly, reduce it immediately, never write it back.
The (n, n) matrix is never formed; peak memory is O(n·d) for X plus the
O(n) frontier state, which is what lets *exact* VAT reach the sizes that
previously forced the sampled (approximate) rungs.

Per grid step b (one VMEM tile of B points):

  * X tile (B, d) and the pivot row x_q (1, d) are staged HBM->VMEM;
    the cross term is a single (B, d) x (d, 1) MXU matvec (Gram trick,
    same decomposition as ``kernels/pairwise_dist.py``), or a broadcast
    |diff| reduce for manhattan — all ``kernels.ref.METRICS`` dispatch
    statically, each compiling its own tile.
  * the min-update and the per-block masked (min, argmin) pair happen on
    the VPU in the same pass; the tiny (nblk,) cross-block reduction runs
    in the jit'd wrapper, first-index tie-breaking preserved.
  * TPU layout: the (n,) frontier vectors travel as lane-dense (1, n)
    rows in (1, B) blocks, and the per-block pair as full lane rows
    (``prim_update.pair_specs``) — the compiler refuses (1,) blocks.

VMEM budget at the default B=1024, d<=512: X tile 1024*512*4B = 2 MiB
plus four (B,) vectors — far under the 16 MiB core.  The batched grid
(b, nblk) follows the slab-of-1 BlockSpec pattern of
``pairwise_dist_pallas_batch``: per-program VMEM stays at the unbatched
budget regardless of the batch size.

Padding: padded rows (X zeros) DO produce computed distances, but their
``selected`` lanes are padded True and their ``mind`` lanes +inf, so
they can never win the argmin; ``core.vat.vat_matrix_free`` keeps its
frontier state padded across the whole loop, so nothing is re-padded
per step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.prim_update import first_argmin, pair_specs, write_pair
from repro.kernels import ref
from repro.kernels.ref import check_metric
from repro.numerics.condition import check_form

DEFAULT_BLOCK = 1024
_LANE = 128  # MXU/VREG lane width — pad d to a multiple


def _tile_pivot_row(x, xq, aux, auxq, metric, form):
    """((B, d), (1, d), (1, B), scalar) -> (1, B) dissimilarities to the pivot.

    Mirrors ``kernels.ref.pivot_row_ref`` term for term so the fused path
    reproduces the XLA path's orderings (same formula, same clamps).
    ``form == "direct"`` (euclidean/sqeuclidean under the safe/auto
    numerics policies) replaces the MXU matvec with a broadcast squared
    -difference reduce — no Gram cancellation, at a VPU-bound cost.  The
    row comes out lane-dense, (1, B), the layout the frontier state
    keeps on the TPU.
    """
    lanes = (1, x.shape[0])
    if metric == "manhattan":
        return jnp.sum(jnp.abs(x - xq), axis=-1).reshape(lanes)
    if form == "direct" and metric != "cosine":
        diff = x - xq
        sq = jnp.sum(diff * diff, axis=-1).reshape(lanes)
        return jnp.sqrt(sq) if metric == "euclidean" else sq
    cross = jax.lax.dot_general(            # MXU: (B, d) x (1, d)^T
        x, xq, (((1,), (1,)), ((), ())), precision=ref.GRAM_PRECISION,
        preferred_element_type=jnp.float32).reshape(lanes)
    if metric == "cosine":
        denom = jnp.maximum(aux * auxq, 1e-12)
        return jnp.clip(1.0 - cross / denom, 0.0, 2.0)
    sq = jnp.maximum(aux + auxq - 2.0 * cross, 0.0)
    return jnp.sqrt(sq) if metric == "euclidean" else sq


def _fold_block(x, xq, aux, auxq, mind, sel, metric, form):
    """One block's fused step: (new frontier (1, B), min, local argmin)."""
    row = _tile_pivot_row(x, xq, aux, auxq, metric, form)
    new = jnp.minimum(mind, row)                # Prim min-update, fused
    m, i = first_argmin(jnp.where(sel != 0, jnp.inf, new))
    return new, m, i


def _prim_stream_kernel(x_ref, xq_ref, aux_ref, auxq_ref, mind_ref, sel_ref,
                        newmind_ref, minv_ref, mini_ref, *, metric, form):
    b = pl.program_id(0)
    x = x_ref[...].astype(jnp.float32)          # (B, d)
    new, m, i = _fold_block(x, xq_ref[...].astype(jnp.float32),
                            aux_ref[...], auxq_ref[...], mind_ref[...],
                            sel_ref[...], metric, form)
    newmind_ref[...] = new
    write_pair(minv_ref, mini_ref, m, i + b * x.shape[0])


def _prim_stream_kernel_batch(x_ref, xq_ref, aux_ref, auxq_ref, mind_ref,
                              sel_ref, newmind_ref, minv_ref, mini_ref, *,
                              metric, form):
    j = pl.program_id(1)
    x = x_ref[0].astype(jnp.float32)            # (1, B, d) slab -> (B, d)
    new, m, i = _fold_block(x, xq_ref[0].astype(jnp.float32), aux_ref[0],
                            auxq_ref[0], mind_ref[0], sel_ref[0], metric,
                            form)
    newmind_ref[0] = new
    write_pair(minv_ref, mini_ref, m, i + j * x.shape[0])


def pad_points(X: jax.Array, aux: jax.Array, *, block: int = DEFAULT_BLOCK):
    """Pad (X, aux) once so every later fused step runs pad-free.

    Args:
      X: (n, d) float — data points.
      aux: (n,) float32 — ``kernels.ref.metric_aux_ref`` of X.
      block: the tile length the steps will use (static).

    Returns:
      (Xp (..., n_pad, d_pad) f32, auxp (..., n_pad) f32, n_pad,
      block_clamped) — n padded to a multiple of the clamped block, d to
      the 128-lane width; leading (batch) axes pass through untouched.
      Padded rows are zero; the caller masks them via its frontier state
      (selected=True, mind=+inf), never via the kernel.
    """
    n, d = X.shape[-2:]
    bn = min(block, max(8, n))
    n_pad = -(-n // bn) * bn
    d_pad = -(-d // _LANE) * _LANE
    lead = [(0, 0)] * (X.ndim - 2)
    Xp = jnp.pad(X.astype(jnp.float32),
                 lead + [(0, n_pad - n), (0, d_pad - d)])
    auxp = jnp.pad(aux.astype(jnp.float32), lead + [(0, n_pad - n)])
    return Xp, auxp, n_pad, bn


def _stream_call(Xp, xq, auxp, auxq, mind, selected, *, metric, form, block,
                 interpret):
    """Shared pallas_call of the solo fused step: pivot passed by value.

    Both front doors use it — ``prim_stream_step_pallas`` (pivot given as
    an index into Xp, the stepwise Flash-VAT engine) and
    ``prim_frontier_step_pallas`` (pivot given as a point, the sharded
    engine where the pivot row arrives by collective broadcast).
    """
    n_pad, d_pad = Xp.shape
    nblk = n_pad // block
    out_specs, out_shape = pair_specs(nblk)
    row = pl.BlockSpec((1, block), lambda b: (0, b))
    new_mind, minv, mini = pl.pallas_call(
        functools.partial(_prim_stream_kernel, metric=metric, form=form),
        grid=(nblk,),
        in_specs=[
            pl.BlockSpec((block, d_pad), lambda b: (b, 0)),
            pl.BlockSpec((1, d_pad), lambda b: (0, 0)),
            row,
            pl.BlockSpec((1, 1), lambda b: (0, 0)),
            row,
            row,
        ],
        out_specs=[row, *out_specs],
        out_shape=[jax.ShapeDtypeStruct((1, n_pad), jnp.float32),
                   *out_shape],
        interpret=interpret,
    )(Xp, xq, auxp[None], auxq.reshape(1, 1), mind[None],
      selected.astype(jnp.int32)[None])
    minv, mini = minv[:, 0, 0], mini[:, 0, 0]
    best = jnp.argmin(minv)         # (nblk,) cross-block pass, negligible
    return new_mind[0], minv[best], mini[best]


@functools.partial(jax.jit,
                   static_argnames=("metric", "form", "block", "interpret"))
def prim_frontier_step_pallas(
    Xp: jax.Array,
    auxp: jax.Array,
    xq: jax.Array,
    auxq: jax.Array,
    mind: jax.Array,
    selected: jax.Array,
    *,
    metric: str = "euclidean",
    form: str = "gram",
    block: int = DEFAULT_BLOCK,
    interpret: bool = False,
):
    """The fused step with the pivot passed by VALUE instead of index.

    The sharded matrix-free engine's per-device kernel: the pivot row
    usually lives on another device and arrives via a psum broadcast, so
    there is no local index to gather.  Same kernel, same tile math,
    same first-index tie-breaking as ``prim_stream_step_pallas``.

    Args:
      Xp: (n_pad, d_pad) f32 — the device's padded local points.
      auxp: (n_pad,) f32 — padded local auxiliary vector.
      xq: (d_pad,) f32 — the (padded) pivot point.
      auxq: f32 scalar — the pivot's ``metric_aux_ref`` entry.
      mind / selected / metric / form / block / interpret: as in
        ``prim_stream_step_pallas``.

    Returns:
      (new_mind (n_pad,) f32, value f32, idx i32) — the folded frontier
      (selected lanes carry ``min(mind, row)`` like the stepwise kernel;
      in-band callers re-mask, see ``kernels.ops.prim_frontier_step``)
      and its masked (min, argmin) pair.
    """
    check_metric(metric)
    check_form(form)
    return _stream_call(Xp, xq.reshape(1, -1), auxp, auxq.reshape(1),
                        mind, selected, metric=metric, form=form,
                        block=block, interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("metric", "form", "block", "interpret"))
def prim_stream_step_pallas(
    Xp: jax.Array,
    auxp: jax.Array,
    q: jax.Array,
    mind: jax.Array,
    selected: jax.Array,
    *,
    metric: str = "euclidean",
    form: str = "gram",
    block: int = DEFAULT_BLOCK,
    interpret: bool = False,
):
    """One fused Prim step over pre-padded points (see ``pad_points``).

    Args:
      Xp: (n_pad, d_pad) f32 — padded data points.
      auxp: (n_pad,) f32 — padded metric auxiliary vector.
      q: i32 scalar (traced ok) — pivot selected by the previous step;
        its row x_q is gathered here (O(d)) and broadcast to every tile.
      mind: (n_pad,) f32 — frontier distances before folding in q's row;
        padded lanes must be +inf.
      selected: (n_pad,) bool — True lanes excluded from the argmin
        (already visited + padding).
      metric: one of ``kernels.ref.METRICS`` (static).
      form: "gram" (default) or "direct" — the numerics-policy tile
        form (static; see ``_tile_pivot_row``).
      block: VMEM tile length (static; must divide n_pad — use the
        clamped block ``pad_points`` returns).
      interpret: Pallas interpret mode (CPU correctness path).

    Returns:
      (new_mind (n_pad,) f32, edge f32 scalar, next i32 scalar) —
      matching ``kernels.ref.prim_stream_step_ref`` on the unpadded
      prefix: the updated frontier, the next vertex's MST edge weight,
      and the next vertex index (first-index tie-breaking across and
      within blocks).
    """
    check_metric(metric)
    check_form(form)
    xq = jax.lax.dynamic_slice_in_dim(Xp, q, 1, axis=0)        # (1, d_pad)
    auxq = jax.lax.dynamic_slice_in_dim(auxp, q, 1, axis=0)    # (1,)
    return _stream_call(Xp, xq, auxp, auxq, mind, selected, metric=metric,
                        form=form, block=block, interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("metric", "form", "block", "interpret"))
def prim_stream_step_pallas_batch(
    Xp: jax.Array,
    auxp: jax.Array,
    q: jax.Array,
    mind: jax.Array,
    selected: jax.Array,
    *,
    metric: str = "euclidean",
    form: str = "gram",
    block: int = DEFAULT_BLOCK,
    interpret: bool = False,
):
    """Batched fused Prim step: b independent frontiers, one pallas_call.

    The grid grows a leading batch axis, (b, nblk), and every BlockSpec
    gains a size-1 slab dim indexed by the batch coordinate — the same
    pattern as ``pairwise_dist_pallas_batch``, so per-program VMEM stays
    at the unbatched budget regardless of b.

    Args:
      Xp: (b, n_pad, d_pad) f32 — padded datasets.
      auxp: (b, n_pad) f32 — padded per-dataset auxiliary vectors.
      q: (b,) i32 — per-dataset pivot from the previous step.
      mind: (b, n_pad) f32 — per-dataset frontiers (padding +inf).
      selected: (b, n_pad) bool — per-dataset visited masks (padding True).
      metric, form, block, interpret: as in ``prim_stream_step_pallas``.

    Returns:
      (new_mind (b, n_pad) f32, edge (b,) f32, next (b,) i32) — each lane
      bitwise-identical to the solo step on its own dataset (no
      cross-dataset reduction exists anywhere).
    """
    check_metric(metric)
    check_form(form)
    b, n_pad, d_pad = Xp.shape
    nblk = n_pad // block
    xq = jax.vmap(
        lambda x, i: jax.lax.dynamic_slice_in_dim(x, i, 1, 0))(Xp, q)
    auxq = jax.vmap(
        lambda a, i: jax.lax.dynamic_slice_in_dim(a, i, 1, 0))(auxp, q)

    out_specs, out_shape = pair_specs(nblk, lead=(b,))
    row = pl.BlockSpec((1, 1, block), lambda bi, j: (bi, 0, j))
    new_mind, minv, mini = pl.pallas_call(
        functools.partial(_prim_stream_kernel_batch, metric=metric,
                          form=form),
        grid=(b, nblk),
        in_specs=[
            pl.BlockSpec((1, block, d_pad), lambda bi, j: (bi, j, 0)),
            pl.BlockSpec((1, 1, d_pad), lambda bi, j: (bi, 0, 0)),
            row,
            pl.BlockSpec((1, 1, 1), lambda bi, j: (bi, 0, 0)),
            row,
            row,
        ],
        out_specs=[row, *out_specs],
        out_shape=[jax.ShapeDtypeStruct((b, 1, n_pad), jnp.float32),
                   *out_shape],
        interpret=interpret,
    )(Xp, xq, auxp[:, None], auxq[:, None], mind[:, None],
      selected.astype(jnp.int32)[:, None])
    minv, mini = minv[..., 0, 0], mini[..., 0, 0]
    best = jnp.argmin(minv, axis=1)                      # (b,) per lane
    lane = jnp.arange(b)
    return new_mind[:, 0], minv[lane, best], mini[lane, best]
