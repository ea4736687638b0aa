"""Pallas TPU megakernel: the ENTIRE Prim traversal in one pallas_call.

The stepwise Flash-VAT engine (``kernels/prim_stream.py``) removed the
O(n^2) memory wall but kept a time wall: n-1 separate ``pallas_call``
dispatches, each round-tripping the O(n) frontier state through HBM.
This module is the Turbo layer — ONE persistent kernel that:

  * keeps every piece of traversal state on-core for the whole run: the
    frontier ``mind`` (selected lanes in-band as +inf) and the per-tile
    pruning state (``tmin``/``pend_lb``/``nfold``) in VMEM, the
    ``order``/``edges`` outputs in SMEM;
  * streams X tiles HBM->VMEM on demand with explicit DMA (X lives in
    ``ANY`` memory space; only one (block, d_pad) tile plus the pivot
    row's 8-row group is ever resident), so VMEM stays O(n + block·d);
  * prunes with per-tile frontier lower bounds (lazy Prim): a tile whose
    bound provably exceeds the best exact candidate skips its distance
    recompute — and its DMA — entirely this step.  On clustered data
    most steps touch ~1 of n/block tiles, a data-dependent ~(n/block)x
    HBM-traffic cut over the eager stepwise engine.

Lazy-fold exactness argument (why pruning cannot change the ordering):

  * f32 ``min`` is exact (no rounding), so folding pivot rows into a
    tile in any order — or arbitrarily late — produces bitwise-identical
    frontier values; per-(pivot, lane) row values come from the same
    Gram-trick formula as ``ref.pivot_row_ref``.
  * per tile T the kernel tracks ``tmin[T]`` (min of its stored, possibly
    stale frontier lanes) and ``pend_lb[T]`` (a lower bound on every
    pending, unfolded pivot's distance to any lane of T, from the tile's
    centroid + radius via the triangle inequality — both computed in the
    direct difference form — shrunk by ``_LB_MARGIN`` against relative
    f32 rounding AND debited ``lb_slack_ulps(form)·eps·max‖x‖²``
    against the absolute cancellation error of the Gram-trick rows it
    is compared with — 4 ulps suffice for direct-form rows, which have
    no cancellation).  ``min(tmin, pend_lb)`` lower-bounds T's computed
    frontier min.
  * per step, tiles are folded in ascending-bound order until every
    unfolded tile's bound strictly exceeds the best exact candidate.
    Stale lanes then provably exceed the winner too (stale >= true >
    best), so the global first-index argmin over the stored frontier is
    exactly the eager argmin — ties included.

Metric geometry of the bound: euclidean/manhattan are metrics, so
``d(q, x) >= d(q, c_T) - r_T`` directly; sqeuclidean bounds in euclidean
space and squares; cosine is not a metric here, so its radius is +inf
and the bound degrades to 0 — correct, just never prunes.

TPU layout.  The frontier is a lane-dense (1, n) VMEM scratch that the
kernel reads and writes through ref slices: a tile at a block-aligned
offset, a single lane through its aligned 128-lane chunk (the TPU
lowers no ``dynamic_slice`` of a value and no unaligned dynamic lane
access).  Per-tile state is an (nblk, 1) column updated by masked
selects.  ``order`` and ``edges`` are SMEM outputs written one scalar
per step, which caps n by ``PERSIST_SMEM_BUDGET``; the seed vertex
arrives via an SMEM (1,) block.  Padded lanes are +inf in-band from
step 0 and can never win; padded tail columns of X are zeros, which
contribute exact 0.0 terms to every dot product.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.prim_stream import _LANE, DEFAULT_BLOCK, _tile_pivot_row
from repro.kernels.ref import UNSEEN, check_metric
from repro.numerics.condition import check_form, lb_slack_ulps

#: VMEM the persistent kernel may plan for (bytes): the compiler's
#: 16 MiB scoped-VMEM limit on v5e less 1 MiB of headroom.
#: ``persist_state_bytes`` is fitted to what compiling for a described
#: v5e shows (see its docstring), so the guard admits only shapes that
#: compile.
PERSIST_VMEM_BUDGET = 15 * 1024 * 1024

#: SMEM the order/edges outputs may take (bytes): the v5e core has
#: 1 MiB of SMEM, and the compiler refuses a kernel that asks for more.
PERSIST_SMEM_BUDGET = 960 * 1024

#: Relative safety factor applied to every pruning lower bound.  The
#: bound math (direct-form centroid distance minus radius) carries a few
#: ulp of f32 rounding; shrinking it 1e-3 relative keeps it a true lower
#: bound with ~100x margin while costing no measurable pruning power (a
#: tile within 0.1% of the winner would be folded next step anyway).
_LB_MARGIN = 0.999

#: Absolute-error allowance for the frontier-row side of the comparison:
#: ``numerics.condition.lb_slack_ulps(form)`` ulps at scale max‖x‖².
#: Gram-form rows (``_tile_pivot_row``'s aux + aux_q - 2·cross
#: decomposition) carry ABSOLUTE cancellation error — up to
#: ~C·eps·max‖x‖² regardless of how small the distance is — so a
#: relative margin alone is unsound on uncentered data; the kernel
#: subtracts ``lb_slack_ulps(form) · eps · max(aux)`` in squared
#: -distance units from every bound (its sqrt in euclidean units).
#: The gram value (64) is the original PR-5 constant, now shared with
#: the ``KAPPA_SAFE`` derivation; direct-form rows have no cancellation
#: and keep only a tiny final-sum allowance (4).
_F32_EPS = float(jnp.finfo(jnp.float32).eps)

_SUB = 8  # f32 sublane tile: rows are fetched in aligned groups of 8


def _persist_block(n: int, block: int) -> int:
    """Tile length of an n-point run: ``block`` rounded up to whole
    128-lane rows, capped at n rounded up the same way.  Tiles are lane
    slices of the (1, n) frontier at multiples of the tile length, and
    the TPU takes a dynamic lane slice only at a 128-aligned offset."""
    return min(-(-block // _LANE) * _LANE, -(-max(n, 1) // _LANE) * _LANE)


def persist_state_bytes(n: int, d: int, *, block: int = DEFAULT_BLOCK) -> int:
    """VMEM bytes the persistent kernel keeps resident for an (n, d) run.

    Mirrors the wrapper's padding arithmetic, with coefficients fitted
    to the scoped VMEM the v5e compiler allocates: 12 lane-dense f32
    rows per padded point (the frontier scratch, aux, the iota and the
    per-step compare/select temporaries), the X tile three times (DMA
    scratch, loaded value, one elementwise temporary), the per-tile
    centroids and bounds, and the pivot row group.  X itself stays in
    HBM and is streamed tile-by-tile; order/edges live in SMEM
    (``persist_smem_bytes``).  At n = 100 000 the compile overflows
    16 MiB at d = 1024 (cosine) and fits at d = 512; at n = 50 000 it
    fits at d = 1280 and overflows at d = 1536.

    Args:
      n: real point count.
      d: feature count.
      block: tile length the kernel will use.

    Returns:
      bytes — compare against ``PERSIST_VMEM_BUDGET``.
    """
    bn = _persist_block(n, block)
    n_pad = -(-n // bn) * bn
    n_lane = -(-n_pad // _LANE) * _LANE
    d_pad = -(-d // _LANE) * _LANE
    nblk = n_pad // bn
    per_point = 12 * 4 * n_lane
    per_tile = nblk * (d_pad * 4 + 5 * 4)  # centroid row + rad/tmin/pend/nfold
    scratch = (3 * bn + _SUB) * d_pad * 4
    return per_point + per_tile + scratch


def persist_smem_bytes(n: int) -> int:
    """SMEM bytes of an n-point run: the order and edges outputs, which
    the kernel writes one scalar per step."""
    return 2 * 4 * n + 64


def persist_supported(n: int, d: int, *, block: int = DEFAULT_BLOCK) -> bool:
    """True when the resident state fits ``PERSIST_VMEM_BUDGET`` and the
    outputs fit ``PERSIST_SMEM_BUDGET``.

    The dispatch guard ``kernels.ops.prim_persist`` consults; above the
    seam the XLA mirror (``ref.prim_persist_ref``) — never the stepwise
    engine — takes over.
    """
    return (persist_state_bytes(n, d, block=block) <= PERSIST_VMEM_BUDGET
            and persist_smem_bytes(n) <= PERSIST_SMEM_BUDGET)


def persist_tile_bounds(Xp: jax.Array, n: int, *, metric: str,
                        block: int):
    """Per-tile (centroid, radius) for the pruning bounds.

    Args:
      Xp: (n_pad, d_pad) f32 — zero-padded points.
      n: real point count (padded lanes are excluded from the geometry).
      metric: one of ``kernels.ref.METRICS``.
      block: tile length (must divide n_pad).

    Returns:
      (cent (nblk, d_pad) f32, rad (nblk,) f32): per-tile mean point and
      tile radius in the bound's geometry — euclidean for
      euclidean/sqeuclidean, L1 for manhattan, +inf for cosine (which
      disables pruning; cosine dissimilarity has no triangle inequality
      to lean on).  Both sides are computed in the DIRECT difference
      form, so their errors are relative and the kernel's _LB_MARGIN
      covers them.
    """
    check_metric(metric)
    n_pad, d_pad = Xp.shape
    nblk = n_pad // block
    tiles = Xp.reshape(nblk, block, d_pad)
    real = (jnp.arange(n_pad).reshape(nblk, block) < n)
    cnt = jnp.maximum(jnp.sum(real, axis=1), 1).astype(jnp.float32)
    cent = jnp.sum(tiles * real[..., None], axis=1) / cnt[:, None]
    if metric == "cosine":
        rad = jnp.full((nblk,), jnp.inf, jnp.float32)
    elif metric == "manhattan":
        dist = jnp.sum(jnp.abs(tiles - cent[:, None, :]), axis=-1)
        rad = jnp.max(jnp.where(real, dist, -jnp.inf), axis=1)
    else:
        diff = tiles - cent[:, None, :]
        dist = jnp.sqrt(jnp.maximum(jnp.sum(diff * diff, axis=-1), 0.0))
        rad = jnp.max(jnp.where(real, dist, -jnp.inf), axis=1)
    return cent, jnp.maximum(rad, 0.0)


def _persist_kernel(i0_ref, aux_ref, cent_ref, rad_ref, tmin_ref, x_ref,
                    order_ref, edges_ref, stats_ref, mind_ref, tile_ref,
                    row_ref, sem_t, sem_r, *, n, metric, form, block, prune):
    n_lane = aux_ref.shape[1]
    nblk = cent_ref.shape[0]
    cent = cent_ref[...]                                    # (nblk, d_pad)
    rad = rad_ref[...]                                      # (nblk, 1)
    iota = lax.broadcasted_iota(jnp.int32, (1, n_lane), 1)
    blk_iota = lax.broadcasted_iota(jnp.int32, (nblk, 1), 0)
    lane = lax.broadcasted_iota(jnp.int32, (1, _LANE), 1)
    i0 = i0_ref[0]
    inf = jnp.float32(jnp.inf)

    sub = lax.broadcasted_iota(jnp.int32, (_SUB, 1), 0)

    def fetch_row(p):
        """Point p's (padded) row, (1, d_pad): DMA the aligned 8-row group
        holding it (the TPU refuses a 1-row slice of a tiled buffer) and
        select the row by a masked sum, which adds exact zeros."""
        p8 = pl.multiple_of((p // _SUB) * _SUB, _SUB)
        cp = pltpu.make_async_copy(x_ref.at[pl.ds(p8, _SUB)], row_ref, sem_r)
        cp.start()
        cp.wait()
        return jnp.sum(jnp.where(sub == p - p8, row_ref[...], 0.0), axis=0,
                       keepdims=True)

    def chunk_at(p):
        """Start of the 128-lane chunk holding lane p: the TPU has no
        load or store at an unaligned dynamic lane, so single lanes are
        read and written through their aligned chunk."""
        return pl.multiple_of((p // _LANE) * _LANE, _LANE)

    def aux_at(p):
        c = chunk_at(p)                 # masked sum adds exact zeros
        return jnp.sum(jnp.where(lane == p - c, aux_ref[:, pl.ds(c, _LANE)],
                                 0.0))

    def tile_start(T):
        return 0 if nblk == 1 else pl.multiple_of(T * block, block)

    def pick(vec, T):
        """vec[T] of a per-tile (nblk, 1) int vector."""
        return jnp.sum(jnp.where(blk_iota == T, vec, 0))

    # row-side cancellation allowance, squared-distance units, per tile
    # form (the module constants explain why a relative margin alone is
    # unsound against Gram rows)
    slack_sq = (jnp.float32(lb_slack_ulps(form) * _F32_EPS)
                * jnp.max(aux_ref[...]))

    def tile_lb(xq):
        """Lower bound on d(q, any lane of tile T) for every T: triangle
        inequality off the tile centroid — DIRECT-form centroid distance
        (relative error only, matching the radius computation), shrunk
        by _LB_MARGIN and debited the Gram slack.  xq is (1, d_pad);
        the bound is (nblk, 1)."""
        diff = cent - xq
        if metric == "manhattan":
            dq = jnp.sum(jnp.abs(diff), axis=-1, keepdims=True)
        else:
            dq = jnp.sqrt(jnp.maximum(
                jnp.sum(diff * diff, axis=-1, keepdims=True), 0.0))
        e = jnp.maximum(dq - rad, 0.0) * jnp.float32(_LB_MARGIN)
        if metric == "euclidean":
            lb = jnp.maximum(e - jnp.sqrt(slack_sq), 0.0)
        elif metric == "sqeuclidean":
            lb = jnp.maximum(e * e - slack_sq, 0.0)
        else:               # manhattan: direct |diff| sums both sides —
            lb = e          # no cancellation, margin alone covers it
        if not prune:       # pruning disabled: bound 0 folds every tile
            lb = lb * 0.0
        return lb

    # frontier init: +inf = selected or padding, UNSEEN = no fold yet
    mind_ref[...] = jnp.where((iota >= n) | (iota == i0), inf,
                              jnp.float32(UNSEEN))
    order_ref[0] = i0
    edges_ref[0] = jnp.float32(0.0)

    def fold_tile(T, t, tmin, pend, nfold, fetches, folds):
        """Fold every pending pivot (order[nfold[T]:t]) into tile T."""
        start = tile_start(T)
        cp = pltpu.make_async_copy(x_ref.at[pl.ds(start, block)], tile_ref,
                                   sem_t)
        cp.start()
        cp.wait()
        tile = tile_ref[...]
        aux_t = aux_ref[:, pl.ds(start, block)]
        k0 = pick(nfold, T)

        def fold_one(k, mt):
            p = order_ref[k]
            xp = fetch_row(p)                               # (1, d_pad)
            # the stream kernel's own tile formula — term-for-term (and
            # dot-shape-for-dot-shape) identical rows across both Pallas
            # engines, so near-tie metrics cannot flip between them on
            # 1-ulp dot-lowering differences
            row = _tile_pivot_row(tile, xp, aux_t, aux_at(p), metric, form)
            return jnp.where(jnp.isinf(mt), inf, jnp.minimum(mt, row))

        mt = lax.fori_loop(k0, t, fold_one, mind_ref[:, pl.ds(start, block)])
        mind_ref[:, pl.ds(start, block)] = mt
        hit = blk_iota == T
        tmin = jnp.where(hit, jnp.min(mt), tmin)
        pend = jnp.where(hit, inf, pend)
        nfold = jnp.where(hit, t, nfold)
        return tmin, pend, nfold, fetches + 1, folds + (t - k0)

    def step(t, carry):
        tmin, pend, nfold, fetches, folds, q = carry
        xq = fetch_row(q)                                   # (1, d_pad)
        pend = jnp.minimum(pend, tile_lb(xq))

        # lazy-fold loop: fold ascending-bound tiles until every unfolded
        # tile provably exceeds the best exact candidate (<= keeps ties
        # exact; fuel bounds the loop — each pass folds one tile).  Dead
        # tiles (tmin == +inf: every lane selected/padding, forever) are
        # excluded outright — their stored lanes can never win, and
        # without the mask their pend bound keeps shrinking toward an
        # active pivot and re-fetches the tile every step for nothing
        def fold_bound(tmin, pend, nfold):
            foldable = (nfold < t) & (tmin < inf)
            return jnp.where(foldable, jnp.minimum(tmin, pend), inf)

        def fold_cond(s):
            fuel, tmin, pend, nfold, _, _ = s
            bound = fold_bound(tmin, pend, nfold)
            best_exact = jnp.min(jnp.where(nfold == t, tmin, inf))
            return (fuel < nblk) & (jnp.min(bound) <= best_exact)

        def fold_body(s):
            fuel, tmin, pend, nfold, fetches, folds = s
            bound = fold_bound(tmin, pend, nfold)
            T = jnp.min(jnp.where(bound == jnp.min(bound), blk_iota, nblk))
            return (fuel + 1, *fold_tile(T, t, tmin, pend, nfold, fetches,
                                         folds))

        _, tmin, pend, nfold, fetches, folds = lax.while_loop(
            fold_cond, fold_body,
            (jnp.int32(0), tmin, pend, nfold, fetches, folds))

        best = jnp.min(jnp.where(nfold == t, tmin, inf))
        winner = jnp.min(jnp.where(mind_ref[...] == best, iota, n_lane))
        c = chunk_at(winner)
        mind_ref[:, pl.ds(c, _LANE)] = jnp.where(
            lane == winner - c, inf, mind_ref[:, pl.ds(c, _LANE)])
        Tw = winner // block
        tmin = jnp.where(blk_iota == Tw,
                         jnp.min(mind_ref[:, pl.ds(tile_start(Tw), block)]),
                         tmin)
        order_ref[t] = winner
        edges_ref[t] = best
        return tmin, pend, nfold, fetches, folds, winner

    carry = (tmin_ref[...], jnp.full((nblk, 1), inf),
             jnp.zeros((nblk, 1), jnp.int32), jnp.int32(0), jnp.int32(0), i0)
    carry = lax.fori_loop(1, n, step, carry)
    stats_ref[0] = carry[3]
    stats_ref[1] = carry[4]


@functools.partial(jax.jit, static_argnames=("metric", "form", "block",
                                             "interpret", "prune"))
def prim_persist_pallas(
    X: jax.Array,
    aux: jax.Array,
    i0: jax.Array,
    *,
    metric: str = "euclidean",
    form: str = "gram",
    block: int = DEFAULT_BLOCK,
    interpret: bool = False,
    prune: bool = True,
):
    """Exact VAT ordering of X in ONE persistent pallas_call.

    Pads X once (to whole tiles and 128 lanes), precomputes the per-tile
    pruning geometry, and hands everything to the megakernel: the whole
    n-1 step Prim recurrence runs inside the kernel with the frontier
    VMEM-resident and X streamed tile-by-tile from ANY/HBM.

    Args:
      X: (n, d) float — data points (unpadded; padding is internal).
      aux: (n,) float32 — ``kernels.ref.metric_aux_ref`` of X.
      i0: i32 scalar — seed vertex (``core.vat._streamed_seed_pivot``).
      metric: one of ``kernels.ref.METRICS`` (static).
      form: "gram" (default) or "direct" — the numerics-policy tile
        form (static); the pruning slack is debited per form via
        ``numerics.condition.lb_slack_ulps``.
      block: X tile length (static); rounded up to a multiple of 128
        and capped at n rounded up to 128 (``_persist_block``).
      interpret: Pallas interpret mode (the CPU correctness path).
      prune: lazy-Prim tile pruning (static).  False forces the eager
        fold-everything schedule — same outputs bit for bit (the pin
        tests/test_turbo.py holds the pruning proof to), only more DMA.

    Returns:
      (order (n,) i32, edges (n,) f32, stats (2,) i32) — the exact
      ordering/edge trace plus the traffic census [tile fetches, pivot
      row folds].  Eager folding costs (n-1)·nblk tile fetches; the gap
      to ``stats[0]`` is what pruning saved.  The spanning tree matches
      ``ref.prim_persist_ref``'s in weight to f32 rounding; the
      orderings match except at near-ties, where 1-ulp differences
      between this kernel's dot lowering and the mirror's can pick the
      other candidate (bitwise-equal in the CPU tests; on a TPU v5e
      they part at many near-ties of a large random-order set).  The
      two Pallas engines share one tile formula, so they never flip
      against each other.

    Callers must keep ``persist_supported(n, d, block=block)`` true —
    ``kernels.ops.prim_persist`` owns that guard.
    """
    check_metric(metric)
    check_form(form)
    n, d = X.shape
    bn = _persist_block(n, block)
    n_pad = -(-n // bn) * bn
    n_lane = -(-n_pad // _LANE) * _LANE
    d_pad = -(-d // _LANE) * _LANE
    Xp = jnp.pad(X.astype(jnp.float32), ((0, n_pad - n), (0, d_pad - d)))
    auxp = jnp.pad(aux.astype(jnp.float32), (0, n_lane - n))[None]
    cent, rad = persist_tile_bounds(Xp, n, metric=metric, block=bn)
    i0 = jnp.asarray(i0, jnp.int32)
    iota = jnp.arange(n_pad)
    tmin0 = jnp.min(jnp.where((iota >= n) | (iota == i0), jnp.inf,
                              UNSEEN).reshape(-1, bn), axis=1)

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    order, edges, stats = pl.pallas_call(
        functools.partial(_persist_kernel, n=n, metric=metric, form=form,
                          block=bn, prune=prune),
        in_specs=[
            smem,                                           # i0
            pl.BlockSpec((1, n_lane), lambda: (0, 0)),      # aux
            pl.BlockSpec((n_pad // bn, d_pad), lambda: (0, 0)),  # cent
            pl.BlockSpec((n_pad // bn, 1), lambda: (0, 0)),  # rad
            pl.BlockSpec((n_pad // bn, 1), lambda: (0, 0)),  # tmin
            pl.BlockSpec(memory_space=pl.ANY),              # X (streamed)
        ],
        out_specs=[smem, smem, smem],
        out_shape=[
            jax.ShapeDtypeStruct((n,), jnp.int32),
            jax.ShapeDtypeStruct((n,), jnp.float32),
            jax.ShapeDtypeStruct((2,), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, n_lane), jnp.float32),   # in-band frontier
            pltpu.VMEM((bn, d_pad), jnp.float32),   # streamed X tile
            pltpu.VMEM((_SUB, d_pad), jnp.float32),  # pivot row's group
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA,
        ],
        interpret=interpret,
    )(i0[None], auxp, cent, rad[:, None], tmin0[:, None], Xp)
    return order, edges, stats
