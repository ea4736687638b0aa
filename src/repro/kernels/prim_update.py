"""Pallas TPU kernel: masked block-argmin for Prim's greedy selection.

The Numba-accelerated hot loop of the paper's MST step is
``argmin_j (not selected[j]) mind[j]``.  On TPU this is a VPU reduction;
the kernel tiles the length-n vector into VMEM blocks, each grid step
emitting a per-block (min, argmin) pair, and the (tiny) cross-block
reduction happens in the jit'd wrapper.  One fused pass replaces the
mask-materialize + global argmin XLA emits on its own.

TPU layout: the vector travels as a lane-dense (1, n) row, and each
block's pair is written as a full (1, 128) lane row of an
(nblk, 1, 128) array — the TPU compiler refuses (1,)-element blocks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK = 1024
_LANE = 128


def first_argmin(row):
    """(min, first index of the min) of a (1, B) row — plain reductions.

    The same value and index as ``jnp.argmin`` on non-NaN input; the TPU
    compiler lowers the two reductions, not a variadic argmin or a
    dynamic gather.
    """
    m = jnp.min(row)
    iota = jax.lax.broadcasted_iota(jnp.int32, row.shape, 1)
    return m, jnp.min(jnp.where(row == m, iota, row.shape[1]))


def write_pair(minv_ref, mini_ref, m, i):
    """Store a block's (min, argmin) pair as full lane rows."""
    minv_ref[...] = jnp.full(minv_ref.shape, m, jnp.float32)
    mini_ref[...] = jnp.full(mini_ref.shape, i, jnp.int32)


def pair_specs(nblk: int, lead: tuple = ()):
    """Out specs and shapes of the per-block pair outputs.

    Args:
      nblk: blocks along the last grid axis.
      lead: leading grid extents (a batch axis) in front of it.

    Returns:
      (out_specs, out_shapes) for the two ``(*lead, nblk, 1, 128)``
      arrays; ``[..., 0, 0]`` of each is the pair.
    """
    shape = (*lead, nblk, 1, _LANE)
    block = (*(1 for _ in lead), 1, 1, _LANE)
    spec = pl.BlockSpec(block, lambda *g: (*g, 0, 0))
    return ([spec, spec], [jax.ShapeDtypeStruct(shape, jnp.float32),
                           jax.ShapeDtypeStruct(shape, jnp.int32)])


def _block_argmin_kernel(vals_ref, mask_ref, minv_ref, mini_ref):
    b = pl.program_id(0)
    vals = vals_ref[...].astype(jnp.float32)       # (1, B)
    masked = jnp.where(mask_ref[...] != 0, jnp.inf, vals)
    m, i = first_argmin(masked)
    write_pair(minv_ref, mini_ref, m, i + b * vals.shape[1])


def _pad_to(a: jax.Array, size: int) -> jax.Array:
    pad = size - a.shape[0]
    if pad == 0:
        return a
    return jnp.pad(a, (0, pad), constant_values=(True if a.dtype == jnp.bool_ else 0))


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def masked_argmin_pallas(
    vals: jax.Array,
    mask: jax.Array,
    *,
    block: int = DEFAULT_BLOCK,
    interpret: bool = False,
):
    """Fused masked argmin over VMEM blocks.

    Args:
      vals: (n,) float — candidate values (Prim frontier distances).
      mask: (n,) bool — True lanes are excluded; padding is masked True
        so it can never win.
      block: VMEM tile length (static; clamped to n).
      interpret: Pallas interpret mode (CPU correctness path).

    Returns:
      (f32 scalar min, i32 scalar global argmin), first-index
      tie-breaking across and within blocks (block-local argmin is
      offset by the block base; the tiny cross-block reduction runs in
      the jit'd wrapper).
    """
    n = vals.shape[0]
    bn = min(block, max(8, n))
    n_pad = -(-n // bn) * bn
    vp = _pad_to(vals, n_pad)[None]
    # padded lanes masked out (True); int32 lanes, which the TPU takes
    mp = _pad_to(mask, n_pad).astype(jnp.int32)[None]
    nblk = n_pad // bn
    out_specs, out_shape = pair_specs(nblk)

    minv, mini = pl.pallas_call(
        _block_argmin_kernel,
        grid=(nblk,),
        in_specs=[
            pl.BlockSpec((1, bn), lambda b: (0, b)),
            pl.BlockSpec((1, bn), lambda b: (0, b)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(vp, mp)
    # cross-block reduction: nblk values, negligible
    minv, mini = minv[:, 0, 0], mini[:, 0, 0]
    best_blk = jnp.argmin(minv)
    return minv[best_blk], mini[best_blk]
