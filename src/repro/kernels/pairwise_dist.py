"""Pallas TPU kernel: blocked pairwise dissimilarity matrix, metric-dispatched.

TPU-native replacement for the paper's Cython flattened-loop distance
computation.  The Cython trick (``R[i*n+j]`` for cache locality) has no TPU
meaning; the equivalent control over memory is the BlockSpec tiling below:

  * grid (n/BM, n/BN); program (i, j) owns output tile R[iBM:(i+1)BM, jBN:...]
  * X row-tile (BM, d) and Y row-tile (BN, d) are staged HBM->VMEM by the
    BlockSpec machinery; d is kept fully resident (padded to 128) so the
    cross term is a single (BM, d) x (d, BN) MXU matmul per tile.
  * accumulation in f32 on the VPU; output cast to the requested dtype on
    the way out.

The per-tile math dispatches on ``metric`` (static, so each variant
compiles its own kernel):

  euclidean / sqeuclidean — Gram trick, one MXU matmul per tile
  cosine                  — same matmul + rsqrt row norms on the VPU
  manhattan               — no matmul form exists; the tile loops over
                            128-lane feature chunks and reduces a
                            (BM, BN, 128) |diff| broadcast per chunk

VMEM budget at the default BM=BN=256, d<=512 (matmul metrics):
  2 * 256*512*4B (tiles) + 256*256*4B (out) ~= 1.3 MiB  << 16 MiB VMEM.
Manhattan's broadcast chunk adds BM*BN*128*4B, so its tile is
(32, 128): 32*128*128*4B = 2 MiB.  The column edge stays 128 because
the TPU compiler takes an output block only if its last dimension is a
multiple of 128 lanes (or the whole array); ``_tile_shape`` owns that
rule for both kernels here.

Zero padding is harmless for every metric, for two different reasons:
padded *features* contribute the reduction identity (0) to dots, squared
diffs and |diffs| alike, so real-row entries are exact; padded *rows* DO
produce computed entries (a partial last tile holds real and padded rows
side by side — e.g. cosine's eps-guard maps zero rows to 1.0), but every
per-element formula reads only its own row pair, and the final
``out[:n, :m]`` slice discards all padded-row output.  Any future
in-kernel reduction *across* a tile must re-prove this (padded rows are
live inside the tile).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import ref
from repro.kernels.ref import check_metric
from repro.numerics.condition import check_form

DEFAULT_BLOCK = 256
_LANE = 128  # MXU/VREG lane width — pad contraction dim to a multiple
_MANHATTAN_BLOCK = 64  # broadcast-chunk metrics pay BM*BN*_LANE VMEM
_BCAST_ROWS = 32  # the pairwise broadcast-chunk tile is (_BCAST_ROWS, _LANE)


def _tile_dissim(x, y, metric, form):
    """(BM, d), (BN, d) -> (BM, BN) dissimilarity tile, f32 accumulate.

    ``form == "direct"`` (euclidean/sqeuclidean under the safe/auto
    numerics policies) trades the single MXU matmul for the manhattan
    -style broadcast-chunk loop over squared differences — no
    cancellation, ~2x slower, and it pays the same BM*BN*_LANE VMEM
    bill (so ``_tile_shape`` sizes it like manhattan).
    """
    if metric == "manhattan":
        acc = jnp.zeros((x.shape[0], y.shape[0]), jnp.float32)
        for k0 in range(0, x.shape[1], _LANE):  # d is static: unrolled
            xc = x[:, k0:k0 + _LANE]
            yc = y[:, k0:k0 + _LANE]
            acc += jnp.sum(jnp.abs(xc[:, None, :] - yc[None, :, :]), axis=-1)
        return acc
    if form == "direct" and metric != "cosine":
        acc = jnp.zeros((x.shape[0], y.shape[0]), jnp.float32)
        for k0 in range(0, x.shape[1], _LANE):  # d is static: unrolled
            dc = x[:, None, k0:k0 + _LANE] - y[None, :, k0:k0 + _LANE]
            acc += jnp.sum(dc * dc, axis=-1)
        return jnp.sqrt(acc) if metric == "euclidean" else acc
    cross = jax.lax.dot_general(                # MXU: (BM, d) x (BN, d)^T
        x, y, (((1,), (1,)), ((), ())), precision=ref.GRAM_PRECISION,
        preferred_element_type=jnp.float32)
    if metric == "cosine":
        nx = jnp.sqrt(jnp.sum(x * x, axis=1))   # (BM,)
        ny = jnp.sqrt(jnp.sum(y * y, axis=1))   # (BN,)
        denom = jnp.maximum(nx[:, None] * ny[None, :], 1e-12)
        return jnp.clip(1.0 - cross / denom, 0.0, 2.0)
    nx = jnp.sum(x * x, axis=1)                 # (BM,)
    ny = jnp.sum(y * y, axis=1)                 # (BN,)
    sq = jnp.maximum(nx[:, None] + ny[None, :] - 2.0 * cross, 0.0)
    return jnp.sqrt(sq) if metric == "euclidean" else sq


def _pairwise_kernel(x_ref, y_ref, o_ref, *, metric, form):
    x = x_ref[...].astype(jnp.float32)          # (BM, d)
    y = y_ref[...].astype(jnp.float32)          # (BN, d)
    o_ref[...] = _tile_dissim(x, y, metric, form).astype(o_ref.dtype)


def _pairwise_kernel_batch(x_ref, y_ref, o_ref, *, metric, form):
    x = x_ref[0].astype(jnp.float32)            # (1, BM, d) slab -> (BM, d)
    y = y_ref[0].astype(jnp.float32)
    o_ref[0] = _tile_dissim(x, y, metric, form).astype(o_ref.dtype)


def _pad_to(a: jax.Array, size: int, axis: int) -> jax.Array:
    pad = size - a.shape[axis]
    if pad == 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return jnp.pad(a, widths)


def _broadcast_tile(metric: str, form: str) -> bool:
    return metric == "manhattan" or (form == "direct" and metric != "cosine")


def _clamp_block(block: int, n: int, metric: str,
                 form: str = "gram") -> int:
    """Square tile edge of the kNN fold (whose (BM, k) output blocks
    take any multiple of 8 rows): ``block`` clamped to n, and to
    ``_MANHATTAN_BLOCK`` for the broadcast-chunk tiles."""
    if _broadcast_tile(metric, form):
        block = min(block, _MANHATTAN_BLOCK)  # broadcast-chunk VMEM bill
    return min(block, max(8, n))


def _tile_shape(block: int, n: int, m: int, metric: str,
                form: str) -> tuple[int, int]:
    """(BM, BN) output tile of an (n, m) dissimilarity matrix.

    BN is the lane edge: 128 for the broadcast-chunk tiles, ``block``
    for the matmul tiles, clamped to m — so it is a multiple of 128 or
    the whole m, the two shapes the TPU compiler accepts.
    """
    if _broadcast_tile(metric, form):
        bm, bn = min(block, _BCAST_ROWS), _LANE
    else:
        bm = bn = block
    return min(bm, max(8, n)), min(bn, max(8, m))


@functools.partial(jax.jit,
                   static_argnames=("metric", "form", "block", "interpret"))
def pairwise_dist_pallas(
    X: jax.Array,
    Y: jax.Array | None = None,
    *,
    metric: str = "euclidean",
    form: str = "gram",
    block: int = DEFAULT_BLOCK,
    interpret: bool = False,
) -> jax.Array:
    """Blocked pairwise dissimilarity matrix via pallas_call.

    Args:
      X: (n, d) float — query points.
      Y: (m, d) float or None — reference points (None: Y = X).
      metric: one of ``kernels.ref.METRICS`` (static — each metric
        compiles its own tile; see the module docstring for the math).
      form: "gram" (default) or "direct" — the numerics-policy tile
        form (static; see ``_tile_dissim`` and ``numerics.resolve``).
      block: output tile edge BM = BN (static; clamped to n/m; the
        broadcast-chunk tiles — manhattan, and direct-form
        euclidean/sqeuclidean — use (``_BCAST_ROWS``, 128), see
        ``_tile_shape``).
      interpret: Pallas interpret mode (CPU correctness path).

    Returns:
      (n, m) float32 dissimilarity matrix. n, m are padded to the block
      and d to the 128-lane width internally; padding lives in sliced-off
      tiles (rows) or contributes the reduction identity (features), so
      it never reaches the caller.
    """
    check_metric(metric)
    check_form(form)
    if Y is None:
        Y = X
    n, d = X.shape
    m = Y.shape[0]
    bm, bn = _tile_shape(block, n, m, metric, form)
    n_pad = -(-n // bm) * bm
    m_pad = -(-m // bn) * bn
    d_pad = -(-d // _LANE) * _LANE
    Xp = _pad_to(_pad_to(X, n_pad, 0), d_pad, 1)
    Yp = _pad_to(_pad_to(Y, m_pad, 0), d_pad, 1)

    out = pl.pallas_call(
        functools.partial(_pairwise_kernel, metric=metric, form=form),
        grid=(n_pad // bm, m_pad // bn),
        in_specs=[
            pl.BlockSpec((bm, d_pad), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, d_pad), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n_pad, m_pad), jnp.float32),
        interpret=interpret,
    )(Xp, Yp)
    return out[:n, :m]


@functools.partial(jax.jit,
                   static_argnames=("metric", "form", "block", "interpret"))
def pairwise_dist_pallas_batch(
    X: jax.Array,
    *,
    metric: str = "euclidean",
    form: str = "gram",
    block: int = DEFAULT_BLOCK,
    interpret: bool = False,
) -> jax.Array:
    """Batched self-dissimilarity matrices for a stack of datasets.

    Args:
      X: (b, n, d) float — b independent datasets of n points each.
      metric: one of ``kernels.ref.METRICS`` (static).
      form: "gram" (default) or "direct" — the numerics-policy tile
        form (static).
      block: output tile edge (BM = BN); clamped to n, and shaped by
        ``_tile_shape`` for the broadcast-chunk tiles.
      interpret: Pallas interpret mode (CPU correctness path).

    Returns:
      (b, n, n) float32 — per-dataset dissimilarity matrices.

    One pallas_call serves the whole stack: the grid grows a leading batch
    axis, (b, n/BM, n/BN), and every BlockSpec gains a size-1 slab dim
    indexed by the batch coordinate — the per-tile compute is shared with
    the unbatched kernel, so VMEM per program stays at the unbatched
    budget regardless of b.
    """
    check_metric(metric)
    check_form(form)
    b, n, d = X.shape
    bm, bn = _tile_shape(block, n, n, metric, form)
    n_pad = -(-n // bm) * bm        # rows and columns pad separately when
    m_pad = -(-n // bn) * bn        # the tile is not square
    d_pad = -(-d // _LANE) * _LANE
    Xd = _pad_to(X, d_pad, 2)

    out = pl.pallas_call(
        functools.partial(_pairwise_kernel_batch, metric=metric, form=form),
        grid=(b, n_pad // bm, m_pad // bn),
        in_specs=[
            pl.BlockSpec((1, bm, d_pad), lambda bi, i, j: (bi, i, 0)),
            pl.BlockSpec((1, bn, d_pad), lambda bi, i, j: (bi, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda bi, i, j: (bi, i, j)),
        out_shape=jax.ShapeDtypeStruct((b, n_pad, m_pad), jnp.float32),
        interpret=interpret,
    )(_pad_to(Xd, n_pad, 1), _pad_to(Xd, m_pad, 1))
    return out[:, :n, :n]
