"""Input admission for the public fit surfaces (ISSUE 9, ladder rung 3).

The kernels' min/argmin folds are silent on non-finite input — a single
NaN row propagates through the Prim frontier and produces a garbage
ordering with no error — and a coalesced serving batch would carry that
garbage into every lane's program.  Admission therefore happens at the
*edges* (``FastVAT.fit``/``fit_many`` and ``TendencyServer.submit``),
before a bad request can reach a kernel or a batch, and it fails with
one typed error:

:class:`InvalidInput` subclasses ``ValueError``, so pre-existing
callers catching ``ValueError`` keep working, while the serving layer
can count admission rejects separately from scheduling errors.

Checks (all O(n·d), folded in one pass over row chunks of about 1 MiB
of f64 each, so admission allocates nothing that grows with n and an
np.memmap table is read, not copied — skippable via
``FastVAT(validate=False)`` for trusted hot loops):

  * dtype is real-numeric (bool/int/float; complex, strings and object
    arrays are rejected rather than silently cast),
  * every value is finite (no NaN / +-Inf),
  * n >= ``MIN_POINTS`` (a VAT ordering of fewer points is degenerate),
  * the points are not all identical (zero variance — every pairwise
    dissimilarity is 0 and the "ordering" is meaningless),
  * under ``metric="cosine"``: no zero-norm rows — the kernels' eps
    -guard silently maps them to distance 1.0 from everything, which
    is a fabricated geometry, not the caller's data.  Skipping
    validation (``validate=False``) keeps the documented eps-guard
    semantics for callers who want exactly that.
"""
from __future__ import annotations

import numpy as np

from repro import spans
from repro.numerics.condition import row_chunks

#: Smallest point count a tendency assessment is defined for.
MIN_POINTS = 4


class InvalidInput(ValueError):
    """A request/dataset was rejected at admission (never reached a
    kernel or a serving batch).  ``reason`` is a stable machine-readable
    tag: "dtype" | "non_finite" | "too_few_points" | "degenerate" |
    "zero_norm"."""

    def __init__(self, reason: str, message: str):
        self.reason = reason
        super().__init__(message)


def _as_real_array(X, name: str) -> np.ndarray:
    arr = np.asarray(X)
    if arr.dtype == object or arr.dtype.kind not in "bifu":
        raise InvalidInput(
            "dtype", f"{name} must be a real numeric array, got dtype "
            f"{arr.dtype}")
    return arr


@spans.span("api.admission")
def validate_points(X, *, batched: bool = False, name: str = "X",
                    metric: str | None = None) -> None:
    """Admission-check an (n, d) point matrix (or (b, n, d) stack).

    Args:
      X: the candidate points.
      batched: expect a (b, n, d) stack instead of (n, d).
      name: how to refer to X in error messages.
      metric: the metric the fit will run, when known — enables
        metric-specific checks (currently: cosine's zero-norm screen).

    Raises:
      InvalidInput: non-numeric dtype, non-finite values, n below
        ``MIN_POINTS``, an all-identical (zero-variance) dataset, or a
        zero-norm row under ``metric="cosine"``.  Batched input names
        the offending lane in the message.
    """
    arr = _as_real_array(X, name)
    want = 3 if batched else 2
    if arr.ndim != want:
        # shape errors stay plain ValueErrors at the callers; admission
        # only guards value-level poison.  Tolerate and let them handle.
        return
    n = arr.shape[-2]
    if n < MIN_POINTS:
        raise InvalidInput(
            "too_few_points",
            f"{name} has n={n} points; a tendency assessment needs at "
            f"least {MIN_POINTS}")
    stack = arr if batched else arr[None]
    floating = arr.dtype.kind == "f"
    cosine = metric == "cosine"
    # A sum of non-negative squares is 0 iff every square is: for
    # f16/f32 and integers that is "every entry is 0" (their smallest
    # nonzero square is a normal f64); wider floats' squares can
    # underflow, so they keep the f64 norms.
    wide = floating and arr.dtype.itemsize > 4
    varied = np.zeros(stack.shape[0], bool)
    zero_lanes = np.zeros(stack.shape[0], bool)
    zero_rows = []
    for start, c in row_chunks(stack):
        if floating and not bool(np.isfinite(c).all()):
            if batched:
                bad = np.flatnonzero(~np.isfinite(arr).all(axis=(1, 2)))
                where = f" (lane(s) {bad.tolist()})"
            else:
                where = ""
            raise InvalidInput(
                "non_finite",
                f"{name} contains non-finite values (NaN/Inf){where}; "
                "clean the data or pass validate=False to skip admission "
                "checks")
        if not varied.all():
            # a column holds two distinct values iff some row differs
            # from the lane's first row; comparing (never subtracting)
            # cannot wrap on narrow integer dtypes
            varied |= (c != stack[:, :1]).any(axis=(1, 2))
        if cosine:
            if wide:
                c64 = np.asarray(c, np.float64)
                zero = np.einsum("...nd,...nd->...n", c64, c64) == 0.0
            else:
                zero = ~c.any(axis=-1)
            zero_lanes |= zero.any(axis=-1)
            if not batched:
                zero_rows.extend((np.flatnonzero(zero[0]) + start).tolist())
    dead = np.flatnonzero(~varied)
    if dead.size:
        if batched:
            raise InvalidInput(
                "degenerate",
                f"{name} lane(s) {dead.tolist()} have zero variance "
                "(all points identical) — tendency is undefined")
        raise InvalidInput(
            "degenerate",
            f"{name} has zero variance (all {n} points identical) — "
            "tendency is undefined")
    if zero_lanes.any():
        if batched:
            where = f" (lane(s) {np.flatnonzero(zero_lanes).tolist()})"
        else:
            where = f" (row(s) {zero_rows})"
        raise InvalidInput(
            "zero_norm",
            f"{name} has zero-norm rows{where}; cosine dissimilarity "
            "is undefined for them (the kernels' eps-guard would "
            "silently map them to distance 1.0 from everything) — "
            "drop the rows or pass validate=False to keep the "
            "eps-guard semantics")


@spans.span("api.admission")
def validate_dissimilarity(D, *, name: str = "D") -> None:
    """Admission-check a precomputed dissimilarity (finite values only;
    shape/symmetry checks stay in ``metrics.as_dissimilarity``)."""
    arr = _as_real_array(D, name)
    if arr.dtype.kind == "f" and not bool(np.isfinite(arr).all()):
        raise InvalidInput(
            "non_finite",
            f"{name} contains non-finite dissimilarities (NaN/Inf); "
            "clean the matrix or pass validate=False")
