"""Pallas TPU kernel: fused iVAT row-update (Havens & Bezdek recurrence).

The XLA path in ``kernels/ref.py::ivat_from_vat_ref`` builds each geodesic
row with ``Dp.at[r].set`` / ``Dp.at[:, r].set`` — every step re-emits a
full-matrix dynamic_update_slice pair, which the VPU executes as two
(n, n) copies.  This kernel keeps the growing D' matrix resident in VMEM
across the whole recurrence and touches only the O(n) row/column actually
written per step:

  * grid (b, n-1): the batch dim first, then one grid step per recurrence
    step r = t + 1.  TPU grids iterate sequentially (last axis fastest),
    which is exactly the dependency order the recurrence needs, and the
    constant index map means each (n, n) slab stays in VMEM for all of
    its n-1 steps (the batch axis revision semantics re-materialize it
    per batch element).
  * each step is three VPU-friendly (1, n) vector ops (masked argmin,
    max-merge, predicated select) plus two O(n) stores — no
    full-matrix traffic.
  * the column store is a read-modify-write of the aligned 128-lane
    column chunk holding column r: the TPU compiler refuses a store at
    an unaligned dynamic lane (``o_ref[0, :, ds(r, 1)]``).  The new
    column is built in column layout from D'[:, j] — read through j's
    aligned chunk, which equals row j because D' is kept exactly
    symmetric — so no row-to-column relayout is needed.

VMEM budget: input slab + output slab = 2 * n^2 * 4 B, so n <= 1024 fits
comfortably (~8.4 MiB with temporaries) and n = 1448 is the hard ceiling
on a 16 MiB core.  ``kernels/ops.py::ivat_from_vat`` falls back to the
XLA path above ``MAX_FUSED_N``; on CPU the kernel runs in interpret mode
for correctness testing, matching ``pairwise_dist.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_LANE = 128   # pad n to a lane multiple so (1, n) rows are VREG-aligned
MAX_FUSED_N = 1024  # keep 2 * n^2 * 4B well under the 16 MiB VMEM core


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _ivat_kernel(rstar_ref, o_ref):
    """One recurrence step r = program_id(1) + 1 on a (1, n, n) slab pair."""
    n = rstar_ref.shape[-1]
    t = pl.program_id(1)
    r = t + 1

    @pl.when(t == 0)
    def _init():  # D'[0, :] = D'[:, 0] = 0 seeds the recurrence
        o_ref[...] = jnp.zeros_like(o_ref)

    row = rstar_ref[0, pl.ds(r, 1), :].astype(jnp.float32)    # (1, n)
    masked = jnp.where(_iota((1, n), 1) < r, row, jnp.inf)  # ordered prefix
    dcut = jnp.min(masked)                          # = R*[r, j], the MST edge
    j = jnp.min(jnp.where(masked == dcut, _iota((1, n), 1), n))  # first argmin
    dpj = o_ref[0, pl.ds(j, 1), :]                  # D'[j, :], (1, n)
    newrow = jnp.where(_iota((1, n), 1) < r, jnp.maximum(dcut, dpj), 0.0)
    o_ref[0, pl.ds(r, 1), :] = newrow
    # column r = row r, built in column layout: D'[:, j] via j's chunk
    lane = _iota((n, _LANE), 1)
    cj = pl.multiple_of((j // _LANE) * _LANE, _LANE)
    dpj_col = jnp.sum(jnp.where(lane == j - cj, o_ref[0, :, pl.ds(cj, _LANE)],
                                0.0), axis=1, keepdims=True)      # (n, 1)
    newcol = jnp.where(_iota((n, 1), 0) < r, jnp.maximum(dcut, dpj_col), 0.0)
    cr = pl.multiple_of((r // _LANE) * _LANE, _LANE)
    o_ref[0, :, pl.ds(cr, _LANE)] = jnp.where(
        lane == r - cr, newcol, o_ref[0, :, pl.ds(cr, _LANE)])


def _pad_square(R: jax.Array, n_pad: int) -> jax.Array:
    """Zero-pad the trailing (n, n) dims of a (b, n, n) stack to n_pad."""
    pad = n_pad - R.shape[-1]
    if pad == 0:
        return R
    return jnp.pad(R, ((0, 0), (0, pad), (0, pad)))


@functools.partial(jax.jit, static_argnames=("interpret",))
def ivat_from_vat_pallas(rstar: jax.Array, *, interpret: bool = False
                         ) -> jax.Array:
    """Fused iVAT transform of VAT-ordered dissimilarities.

    Args:
      rstar: (n, n) or (b, n, n) float — VAT-ordered dissimilarity
        matrix/stack (``core.vat.vat_order`` output order). n is padded to
        a lane multiple internally; padding never enters the recurrence
        because the per-step prefix mask only admits k < r < n.
      interpret: run the kernel in Pallas interpret mode (the CPU
        correctness path; compiled Mosaic on TPU).

    Returns:
      (n, n) or (b, n, n) float32 — geodesic (max-min path) distance
      matrix D', same leading shape as the input.
    """
    squeeze = rstar.ndim == 2
    R = rstar[None] if squeeze else rstar
    b, n, _ = R.shape
    if n < 2:  # recurrence is empty; D' is all zeros
        out0 = jnp.zeros(R.shape, jnp.float32)
        return out0[0] if squeeze else out0
    n_pad = -(-n // _LANE) * _LANE
    Rp = _pad_square(R.astype(jnp.float32), n_pad)

    out = pl.pallas_call(
        _ivat_kernel,
        grid=(b, n - 1),
        in_specs=[pl.BlockSpec((1, n_pad, n_pad), lambda bi, t: (bi, 0, 0))],
        out_specs=pl.BlockSpec((1, n_pad, n_pad), lambda bi, t: (bi, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, n_pad, n_pad), jnp.float32),
        interpret=interpret,
    )(Rp)
    out = out[:, :n, :n]
    return out[0] if squeeze else out
