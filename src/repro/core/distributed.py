"""Distributed VAT — lifting the paper's O(n^2) memory wall with shard_map.

The paper's Limitations section: "VAT requires storage of the full pairwise
dissimilarity matrix ... a bottleneck for n > 10^4".  Two remedies here:

* ``pairwise_dist_sharded``: the n x n matrix is computed and kept sharded
  over the mesh `data` axis (row blocks) — aggregate pod HBM instead of
  one host's RAM (x256 on a 16x16 pod).

* ``dvat``: **matrix-free** distributed VAT.  Points are sharded; the Prim
  loop keeps only the O(n) min-distance frontier (itself sharded) and
  recomputes the needed distance row on the fly each step.  Per-step cost:
  one all_gather of P (value, index) pairs + one psum broadcast of the
  selected point.  Memory is O(n d / P + n / P) per device — no n x n
  object ever exists, so n ~ 10^6+ fits a pod.

* ``vat_matrix_free_sharded``: the Turbo Flash-VAT engine over a mesh —
  dvat's communication pattern married to the solo engine's *bitwise*
  ordering contract: Gram-trick rows (not dvat's direct differences), the
  exact streamed row-max seed, the in-band (+inf) frontier, and the fused
  local step dispatched through ``kernels.ops.prim_frontier_step`` (XLA
  ref or Pallas tile).  Orderings match ``core.vat.vat_matrix_free`` bit
  for bit on any shard count, so the flashvat rung auto-shards when more
  than one device is visible without changing a single answer.

Both run under jit + ``jax.shard_map`` on any mesh axis name (default
"data"); docs/scaling.md has the full vat -> svat -> bigvat -> dvat ->
streaming ladder.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.kernels.ref import row_dissim_ref


class DVATResult(NamedTuple):
    order: jax.Array  # (n,) int32 VAT permutation (replicated)


def pairwise_dist_sharded(X: jax.Array, mesh: Mesh, axis: str = "data"):
    """Distance matrix with rows sharded over `axis`; X gathered per shard."""

    def shard_fn(Xl, Xfull):
        return kops.pairwise_dist(Xl, Xfull)

    fn = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(axis, None), P(None, None)),
        out_specs=P(axis, None))
    return fn(X, X)


def _dvat_shard(Xl: jax.Array, axis: str, exact_start: bool, metric: str):
    """Runs on each shard: Xl is the local (n/P, d) slice of the points."""
    p = lax.axis_index(axis)
    Pn = lax.psum(1, axis)
    nl, d = Xl.shape
    n = nl * Pn
    offset = (p * nl).astype(jnp.int32)
    local_ids = jnp.arange(nl, dtype=jnp.int32) + offset

    def bcast_point(q):
        """Fetch row q of X from whichever shard owns it (one psum)."""
        owner = q // nl
        lq = q - owner * nl
        mine = jnp.where(p == owner, Xl[lq], jnp.zeros((d,), Xl.dtype))
        return lax.psum(mine, axis)

    def dist_to_local(xq):
        return row_dissim_ref(Xl, xq, metric=metric)

    if exact_start:
        # exact VAT start: row of the global max of R (O(n^2 d / P) pass,
        # done in n/P-row chunks against a gathered X)
        Xfull = lax.all_gather(Xl, axis, tiled=True)          # (n, d)
        Rl = kops.pairwise_dist(Xl, Xfull, metric=metric)      # (nl, n)
        local_max = jnp.max(Rl, axis=1)                        # per local row
        li = jnp.argmax(local_max).astype(jnp.int32)
        vals = lax.all_gather(local_max[li], axis)             # (P,)
        idxs = lax.all_gather(li + offset, axis)
        i0 = idxs[jnp.argmax(vals)].astype(jnp.int32)
    else:
        # matrix-free start: farthest point from the global mean
        mean = lax.pmean(jnp.mean(Xl, axis=0), axis)
        dm = row_dissim_ref(Xl, mean, metric=metric)
        li = jnp.argmax(dm).astype(jnp.int32)
        vals = lax.all_gather(dm[li], axis)
        idxs = lax.all_gather(li + offset, axis)
        i0 = idxs[jnp.argmax(vals)].astype(jnp.int32)

    x0 = bcast_point(i0)
    mind0 = dist_to_local(x0)
    sel0 = local_ids == i0
    order0 = jnp.zeros((n,), jnp.int32).at[0].set(i0)

    def body(t, carry):
        mind, selected, order = carry
        masked = jnp.where(selected, jnp.inf, mind)
        li = jnp.argmin(masked).astype(jnp.int32)
        vals = lax.all_gather(masked[li], axis)                # (P,)
        idxs = lax.all_gather(li + offset, axis)
        w = jnp.argmin(vals)                                    # first-index ties
        q = idxs[w].astype(jnp.int32)
        order = order.at[t].set(q)
        xq = bcast_point(q)
        mind = jnp.minimum(mind, dist_to_local(xq))
        selected = selected | (local_ids == q)
        return mind, selected, order

    _, _, order = lax.fori_loop(1, n, body, (mind0, sel0, order0))
    return order


def _flash_shard(Xl: jax.Array, axis: str, n: int, metric: str,
                 use_pallas: bool, block: int):
    """Per-device body of ``vat_matrix_free_sharded``.

    Xl is the local contiguous row block of the padded points.  The
    frontier is in-band (+inf = selected/padding, ``kref.UNSEEN`` = not
    yet folded) and every formula is the solo Turbo engine's, restricted
    to the shard — elementwise and row-local, so the shard's slice of
    each quantity is bitwise-equal to the solo path's.
    """
    p = lax.axis_index(axis)
    nl, d = Xl.shape
    offset = (p * nl).astype(jnp.int32)
    aux_l = kref.metric_aux_ref(Xl, metric=metric)

    def bcast_point(q):
        """Row q of X (+ its aux entry) from whichever shard owns it."""
        owner = q // nl
        lq = q - owner * nl
        mine = jnp.where(p == owner, lax.dynamic_slice_in_dim(Xl, lq, 1, 0),
                         jnp.zeros((1, d), Xl.dtype))
        amine = jnp.where(p == owner,
                          lax.dynamic_slice_in_dim(aux_l, lq, 1, 0),
                          jnp.zeros((1,), aux_l.dtype))
        return lax.psum(mine, axis)[0], lax.psum(amine, axis)[0]

    # ---- seed: the solo streamed row-max scan, rows restricted to the
    # shard, columns over a gathered X copy in (bs, bs) blocks — one
    # O(n·d) gather lives through the seed (freed after), but never an
    # (n/P, n) matrix.  Entries come from the same pairwise front door,
    # the diag is forced exactly zero at GLOBAL coordinates, padded
    # rows/columns are masked out; f32 max is exact, so this blocking
    # reproduces the solo row maxima bit for bit.
    Xfull = lax.all_gather(Xl, axis, tiled=True)            # (n_padP, d)
    nfull = Xfull.shape[0]
    per_entry = 4 * (d if metric == "manhattan" else 1)
    bs = max(8, min(1024, int(((4 << 20) // per_entry) ** 0.5), nl, nfull))
    nl_pad = -(-nl // bs) * bs
    nf_pad = -(-nfull // bs) * bs
    Xlp = jnp.pad(Xl, ((0, nl_pad - nl), (0, 0)))
    Xfp = jnp.pad(Xfull, ((0, nf_pad - nfull), (0, 0)))
    lane = jnp.arange(bs)

    def row_block(i, acc):
        xb = lax.dynamic_slice_in_dim(Xlp, i * bs, bs, 0)
        rids = offset + i * bs + lane                       # global row ids

        def col_block(j, rm):
            yb = lax.dynamic_slice_in_dim(Xfp, j * bs, bs, 0)
            T = kops.pairwise_dist(xb, yb, metric=metric,
                                   use_pallas=use_pallas)
            cids = j * bs + lane
            T = jnp.where(cids[None, :] == rids[:, None], 0.0, T)  # diag
            T = jnp.where(cids[None, :] < n, T, -jnp.inf)          # padding
            return jnp.maximum(rm, jnp.max(T, axis=1))

        rm = lax.fori_loop(0, nf_pad // bs, col_block,
                           jnp.full((bs,), -jnp.inf))
        return lax.dynamic_update_slice_in_dim(acc, rm, i * bs, 0)

    rowmax = lax.fori_loop(0, nl_pad // bs, row_block,
                           jnp.zeros((nl_pad,), jnp.float32))
    lrow = jnp.arange(nl_pad)
    rowmax = jnp.where((lrow < nl) & (lrow + offset < n), rowmax, -jnp.inf)
    li = jnp.argmax(rowmax).astype(jnp.int32)               # local, < nl
    vals = lax.all_gather(rowmax[li], axis)                 # (P,)
    idxs = lax.all_gather(li + offset, axis)
    i0 = idxs[jnp.argmax(vals)].astype(jnp.int32)           # first-index ties

    # ---- Prim loop: local fused frontier step + (min, argmin) reduce.
    # The Pallas step kernel needs its block to divide the lane count,
    # so the state arrays are padded ONCE via pad_points (rows to the
    # clamped block, d to the 128-lane width); padded lanes ride in-band
    # as +inf and can never win, exactly like the solo engine's padding.
    if use_pallas:
        from repro.kernels.prim_stream import pad_points
        Xs, auxs, _, bn = pad_points(Xl, aux_l, block=block)
        d_pad = Xs.shape[1]
    else:
        Xs, auxs, bn, d_pad = Xl, aux_l, block, d
    m = Xs.shape[0]
    lidx_all = jnp.arange(m, dtype=jnp.int32)
    state_ids = lidx_all + offset       # fake ids on pad lanes stay inert:
    mind0 = jnp.where(                  # their mind is +inf forever
        (lidx_all >= nl) | (state_ids >= n) | (state_ids == i0),
        jnp.inf, jnp.float32(kref.UNSEEN))
    order0 = jnp.zeros((n,), jnp.int32).at[0].set(i0)
    edges0 = jnp.zeros((n,), jnp.float32)

    def body(t, carry):
        mind, order, edges, q = carry
        xq, auxq = bcast_point(q)
        if use_pallas:
            xq = jnp.pad(xq, (0, d_pad - d))
        mind, lv, lidx = kops.prim_frontier_step(
            Xs, auxs, xq, auxq, mind, metric=metric, use_pallas=use_pallas,
            block=bn)
        vals = lax.all_gather(lv, axis)                     # (P,)
        idxs = lax.all_gather(lidx + offset, axis)
        w = jnp.argmin(vals)          # first-device ties = first-index ties
        nq = idxs[w].astype(jnp.int32)
        mind = jnp.where(state_ids == nq, jnp.inf, mind)
        return (mind, order.at[t].set(nq), edges.at[t].set(vals[w]), nq)

    _, order, edges, _ = lax.fori_loop(1, n, body,
                                       (mind0, order0, edges0, i0))
    return order, edges


@functools.lru_cache(maxsize=32)
def _flash_sharded_program(mesh: Mesh, axis: str, n: int, metric: str,
                           use_pallas: bool, block: int):
    """Build-and-jit the sharded traversal ONCE per (mesh, config).

    ``shard_map`` closures are fresh objects per call, so wrapping one in
    ``jax.jit`` inline would defeat the jit cache and re-trace the whole
    n-step program on every invocation (review finding: the warmup fit
    paid for nothing).  Caching the jitted callable restores the
    compile-once-run-many behavior the solo engines get from their
    module-level ``@jax.jit``.
    """
    fn = jax.shard_map(
        functools.partial(_flash_shard, axis=axis, n=n, metric=metric,
                          use_pallas=use_pallas, block=block),
        mesh=mesh,
        in_specs=(P(axis, None),),
        out_specs=(P(), P()),             # order/edges replicated
        check_vma=False)
    return jax.jit(fn)


def vat_matrix_free_sharded(X: jax.Array, mesh: Mesh, axis: str = "data", *,
                            metric: str = "euclidean",
                            use_pallas: bool = False, block: int = 1024):
    """Sharded Turbo Flash-VAT: exact matrix-free VAT over a device mesh.

    X is row-sharded over the ``axis`` mesh axis (rows padded to the axis
    size first; padded lanes ride in-band as +inf and can never win).
    Each device runs the fused local frontier step
    (``kernels.ops.prim_frontier_step`` — the XLA reference or the Pallas
    tile kernel, its state padded once to the kernel's block) against
    its O(n/P · d) shard, then one ``(min, argmin)`` all-reduce picks
    the global next vertex and one psum broadcasts its row.  Steady-state
    memory per device is O(n·d/P + n/P); the seed scan additionally
    holds one gathered O(n·d) X copy per device while it runs (streamed
    through (bs, bs) blocks — never an (n/P, n) matrix), freed before
    the traversal.

    The ordering (and edge trace) is bitwise-identical to the solo
    ``vat_matrix_free`` for every metric: shards are contiguous row
    blocks, every per-lane formula is the solo engine's restricted to
    the shard, f32 min folds are exact, and first-device tie-breaking
    over contiguous blocks equals global first-index tie-breaking —
    pinned (1-device and 8-device) in tests/test_turbo.py.

    Args:
      X: (n, d) float — data points; n need NOT divide the axis size
        (rows are padded internally).
      mesh: the device mesh; ``axis`` names the sharding axis.
      axis: mesh axis name (default "data").
      metric: one of ``kernels.ref.METRICS``.
      use_pallas: route the per-device fused step and the seed scan's
        pairwise tiles through the Pallas kernels.
      block: Pallas step-kernel tile length (clamped to the shard size).

    Returns:
      ``core.vat.FlashVATResult`` — order (n,) i32 and edges (n,) f32,
      replicated on every device.
    """
    from repro.core.vat import FlashVATResult
    n, _ = X.shape
    nshards = mesh.shape[axis]
    n_pad = -(-n // nshards) * nshards
    Xf = jnp.pad(X.astype(jnp.float32), ((0, n_pad - n), (0, 0)))
    program = _flash_sharded_program(mesh, axis, n, metric, use_pallas,
                                     block)
    order, edges = program(Xf)
    return FlashVATResult(order=order, edges=edges)


def dvat(X: jax.Array, mesh: Mesh, axis: str = "data", *,
         exact_start: bool = True,
         metric: str = "euclidean") -> DVATResult:
    """Matrix-free distributed VAT ordering of X (n, d).

    n must be divisible by the mesh axis size (pad upstream otherwise).
    exact_start=False skips the O(n^2 d / P) max-pair pass and starts from
    the point farthest from the mean (block structure is unaffected; the
    ordering may start in a different cluster).  ``metric`` picks the
    dissimilarity (one of ``kernels.ref.METRICS``) — every distance row
    is recomputed from points, so any rowwise-computable metric works.
    """
    fn = jax.shard_map(
        functools.partial(_dvat_shard, axis=axis, exact_start=exact_start,
                          metric=metric),
        mesh=mesh,
        in_specs=(P(axis, None),),
        out_specs=P(),  # order replicated (built from all_gathered data)
        check_vma=False)
    return DVATResult(order=jax.jit(fn)(X))
