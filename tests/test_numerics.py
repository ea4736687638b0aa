"""Numerics shield (ISSUE 10 tentpole): condition-aware dispatch,
precision policies, and certified ordering stability.

Pins the whole contract:

* the policy/statistics layer (κ derivation constants, ``as_policy``
  coercion, ``condition_stats`` on solo + batched + pathological input);
* the streamed pre-pass: every ``ConditionStats`` field bitwise equal
  to the whole-array f64 formula, ``resolve`` unchanged, admission
  verdicts across chunk edges, and memory bounded below the table's;
* the conditioning transform's EXACTNESS properties (power-of-2 scale,
  bitwise shift cancellation on exact-arithmetic grid data);
* ``resolve`` planning per mode (fast / safe / auto × metric);
* bf16 storage: quantization shape, certification, the counted
  fallback, and the ``kernels.numerics_trip`` fault site;
* the acceptance pin — ``fit(X)`` vs ``fit(X + c·1)`` BITWISE-equal
  orderings under the default auto policy for |c| up to 1e6, across
  vat / ivat / flashvat / turbo-off / approx, solo and batched;
* cosine zero-norm admission (solo, batched, streaming, and the
  ``validate=False`` escape hatch);
* the certification harness itself (smoke sweep + oracle sanity);
* the serving layer: the resolved plan as ProgramKey material, the
  per-request ``NumericsReport``, and the resilience fallback counter.
"""
import dataclasses
import re
import tracemalloc
import zlib

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from _numerics_data import ADVERSARIAL_NAMES, adversarial, grid_clusters
from repro import faults
from repro.api import FastVAT
from repro.api.validation import InvalidInput, validate_points
from repro.core.streaming import StreamingVAT
from repro.numerics import (CONDITIONED_METRICS, KAPPA_BF16, KAPPA_SAFE,
                            ConditionStats, NumericsPolicy,
                            NumericsReport, as_policy,
                            condition_stats, condition_transform,
                            lb_slack_ulps, resolve)
from repro.numerics.certify import (certify_fit, ordering_excess,
                                    oracle_dissim, sweep)
from repro.numerics import condition
from repro.numerics.condition import (_CHUNK_BYTES, _condition_stats,
                                      _quantize_bf16)
from repro.serve import ServeConfig, TendencyServer, resolve_key


def _near_origin(n=64, d=4, seed=0):
    rng = np.random.default_rng(seed)
    half = n // 2
    return np.concatenate([rng.normal(size=(half, d)),
                           rng.normal(size=(n - half, d)) + 6.0]
                          ).astype(np.float32)


# ------------------------------------------------ policy & constants ----

def test_kappa_safe_is_the_documented_derivation():
    """κ_safe = 1/(1024·eps_f32): 64-ulp Gram error below gap/16."""
    eps = float(np.finfo(np.float32).eps)
    assert KAPPA_SAFE == 1.0 / (1024.0 * eps) == 8192.0
    assert KAPPA_BF16 == 16.0


def test_lb_slack_ulps_per_form():
    """The shared pruning-slack constant: 64 ulps for the cancelling
    Gram decomposition, 4 for the cancellation-free direct form."""
    assert lb_slack_ulps("gram") == 64.0
    assert lb_slack_ulps("direct") == 4.0
    with pytest.raises(ValueError, match="form"):
        lb_slack_ulps("exact")


def test_as_policy_coercion_and_validation():
    p = as_policy("safe")
    assert isinstance(p, NumericsPolicy) and p.mode == "safe"
    assert as_policy(p) is p
    with pytest.raises(TypeError, match="numerics"):
        as_policy(3.14)
    with pytest.raises(ValueError, match="mode"):
        NumericsPolicy(mode="yolo")
    with pytest.raises(ValueError, match="dtype"):
        NumericsPolicy(dtype="f16")


def test_condition_stats_sees_the_offset():
    near = condition_stats(_near_origin())
    far = condition_stats(_near_origin() + 1.0e4)
    assert near.kappa < KAPPA_SAFE < far.kappa
    # centering removes the offset: the post-transform κ is the near one
    assert far.kappa_centered < KAPPA_SAFE
    assert far.max_sq_norm > 1e7 and far.centered_max_sq < 1e3


def test_condition_stats_batched_takes_worst_lane():
    good = _near_origin(seed=1)
    bad = _near_origin(seed=2) + 1.0e4
    st_b = condition_stats(np.stack([good, bad]))
    assert st_b.kappa == condition_stats(bad).kappa
    assert st_b.gap_proxy == min(condition_stats(good).gap_proxy,
                                 condition_stats(bad).gap_proxy)
    with pytest.raises(ValueError, match="shape"):
        condition_stats(np.zeros(5, np.float32))


def test_condition_stats_degenerate_inputs():
    zero = condition_stats(np.zeros((8, 3), np.float32))
    assert zero.kappa == 0.0 and zero.gap_proxy == 0.0
    # all-identical nonzero points: finite norm over zero gap -> inf
    same = condition_stats(np.ones((8, 3), np.float32) * 5.0)
    assert same.kappa == float("inf")


# ------------------------------------- the streamed pre-pass (chunks) ----

def _stats_whole_array(X):
    """The whole-array f64 formula the chunked pre-pass replaced: the
    reference every ``ConditionStats`` field is pinned to bit for bit."""
    Xd = np.asarray(X, np.float64)
    sq = np.einsum("nd,nd->n", Xd, Xd)
    max_sq = float(np.max(sq)) if sq.size else 0.0
    C = Xd - np.mean(Xd, axis=0)
    csq = np.einsum("nd,nd->n", C, C)
    centered_max_sq = float(np.max(csq)) if csq.size else 0.0
    spread = float(np.max(np.ptp(Xd, axis=0))) if Xd.size else 0.0
    n = Xd.shape[0]
    S = Xd[::max(1, n // 256)][:256]
    ssq = np.einsum("nd,nd->n", S, S)
    G = ssq[:, None] + ssq[None, :] - 2.0 * (S @ S.T)
    np.maximum(G, 0.0, out=G)
    off = G[np.triu_indices(S.shape[0], k=1)]
    nz = off[off > 0.0]
    gap = float(np.median(nz)) if nz.size else 0.0
    kappa = max_sq / gap if gap > 0.0 else (0.0 if max_sq == 0.0
                                            else float("inf"))
    kc = centered_max_sq / gap if gap > 0.0 else (
        0.0 if centered_max_sq == 0.0 else float("inf"))
    return ConditionStats(max_sq, centered_max_sq, spread, gap, kappa, kc)


def _condition_stats_whole_array(X):
    arr = np.asarray(X, np.float64)
    if arr.ndim == 2:
        return _stats_whole_array(arr)
    per = [_stats_whole_array(lane) for lane in arr]
    return ConditionStats(
        max(s.max_sq_norm for s in per), max(s.centered_max_sq for s in per),
        max(s.spread for s in per), min(s.gap_proxy for s in per),
        max(s.kappa for s in per), max(s.kappa_centered for s in per))


def _bits(*values):
    return np.asarray(values, np.float64).tobytes()


def _chunk_rows(d):
    return _CHUNK_BYTES // (8 * d)


def _prepass_case(name):
    rng = np.random.default_rng(zlib.crc32(name.encode()))
    r13, r768 = _chunk_rows(13), _chunk_rows(768)
    if name == "below-one-chunk":
        return rng.normal(size=(100, 13)).astype(np.float32)
    if name == "d13-ragged":
        return (rng.normal(size=(2 * r13 + 37, 13)) * 3 + 2).astype(np.float32)
    if name == "d768-ragged":
        return rng.normal(size=(3 * r768 + 5, 768)).astype(np.float32)
    if name == "d768-exact-chunks":
        return rng.normal(size=(2 * r768, 768)).astype(np.float32)
    if name == "far-offset":
        return np.tile(_near_origin(), (600, 1)) + np.float32(1.0e4)
    if name == "batched":
        return np.stack([_near_origin(n=r13 + 3, d=13, seed=s) + off
                         for s, off in ((5, 0.0), (6, 1.0e4), (7, -3.0))])
    if name == "f64":
        return rng.normal(size=(r13 + 11, 13)) * 1e3 + 7.0
    if name == "f64-far-offset":   # f64 sums round: the mean's order shows
        return rng.normal(size=(2 * r13 + 3, 13)) + 1.0e4
    if name == "int16":
        return rng.integers(-30000, 30000, size=(r13 + 9, 13)
                            ).astype(np.int16)
    if name == "int64-batched":
        return rng.integers(-10**6, 10**6, size=(2, 300, 13))
    if name == "fortran-order":
        return np.asfortranarray(
            rng.normal(size=(2 * r13 + 1, 13)).astype(np.float32) + 50.0)
    raise KeyError(name)


_PREPASS_CASES = ("below-one-chunk", "d13-ragged", "d768-ragged",
                  "d768-exact-chunks", "far-offset", "batched", "f64",
                  "f64-far-offset", "int16", "int64-batched", "fortran-order")


@pytest.mark.parametrize("case", _PREPASS_CASES)
def test_condition_stats_streamed_matches_whole_array(case):
    """The chunked pre-pass pins every ``ConditionStats`` field BITWISE
    to the whole-array f64 formula; without the centred pass (what
    ``resolve`` runs for an unconditioned metric) the fields it keeps
    are the same bits and the ones it skips read NaN."""
    X = _prepass_case(case)
    ref = _condition_stats_whole_array(X)
    got = condition_stats(X)
    assert _bits(*dataclasses.astuple(got)) == \
        _bits(*dataclasses.astuple(ref))
    lean = _condition_stats(X, centered=False)
    assert _bits(lean.max_sq_norm, lean.gap_proxy, lean.kappa) == \
        _bits(ref.max_sq_norm, ref.gap_proxy, ref.kappa)
    assert np.isnan([lean.centered_max_sq, lean.spread,
                     lean.kappa_centered]).all()
    if case == "far-offset":
        assert got.kappa > KAPPA_SAFE


@pytest.mark.parametrize("case", ["d13-ragged", "far-offset", "batched",
                                  "f64", "d768-ragged"])
@pytest.mark.parametrize("metric,policy", [
    ("cosine", "auto"), ("euclidean", "auto"), ("manhattan", "safe"),
    ("euclidean", NumericsPolicy(mode="auto", dtype="bf16")),
    ("cosine", NumericsPolicy(mode="safe", dtype="bf16"))])
def test_resolve_unchanged_by_the_streamed_prepass(case, metric, policy,
                                                   monkeypatch):
    """``resolve``'s output array and ``NumericsReport`` are the ones
    the whole-array statistics give, for every metric and policy."""
    X = _prepass_case(case)
    batched = X.ndim == 3
    Xo, rep = resolve(X, metric=metric, policy=policy, batched=batched)
    monkeypatch.setattr(
        condition, "_condition_stats",
        lambda X, centered: _condition_stats_whole_array(X))
    Xr, rep_r = resolve(X, metric=metric, policy=policy, batched=batched)
    assert rep == rep_r and _bits(rep.kappa) == _bits(rep_r.kappa)
    assert Xo.dtype == Xr.dtype == np.float32
    assert Xo.tobytes() == Xr.tobytes()


def test_prepass_memory_is_bounded():
    """Admission plus the statistics on an 8192 x 768 f32 cosine table
    allocate at peak under a quarter of the table's own bytes: no f64
    copy, no whole-array temporary."""
    X = np.random.default_rng(3).normal(size=(8192, 768)).astype(np.float32)
    tracemalloc.start()
    try:
        validate_points(X, metric="cosine")
        Xo, rep = resolve(X, metric="cosine")
        _, peak = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    assert Xo is X and rep.form == "gram"
    assert peak < X.nbytes / 4, (peak, X.nbytes)


# ------------------------------------------------------ the transform ----

def test_condition_transform_scale_is_power_of_two():
    X = _near_origin() * 37.3 + 1234.5
    C = condition_transform(X)
    assert C.dtype == np.float32
    amax = float(np.max(np.abs(C)))
    assert 1.0 <= amax < 2.0
    # the documented formula, replayed: f64 center, exact 2^-k rescale
    spread64 = np.asarray(X, np.float64) - np.mean(
        np.asarray(X, np.float64), axis=0)
    scale = float(np.exp2(-np.floor(np.log2(np.max(np.abs(spread64))))))
    np.testing.assert_array_equal(
        C, np.asarray(spread64 * scale, np.float32))


def test_condition_transform_cancels_exact_shifts_bitwise():
    """The heart of the shift-invariance pin, isolated: on the exact
    -arithmetic grid, transform(X + c) == transform(X) to the bit."""
    X = grid_clusters()
    base = condition_transform(X)
    for c in (1e3, 1e4, 1e6, -1e6):
        shifted = condition_transform(X + np.float32(c))
        np.testing.assert_array_equal(base, shifted)


def test_condition_transform_batched_is_per_lane():
    Xs = np.stack([grid_clusters(seed=0), grid_clusters(seed=1) + 512.0])
    Cb = condition_transform(Xs)
    np.testing.assert_array_equal(Cb[0], condition_transform(Xs[0]))
    np.testing.assert_array_equal(Cb[1], condition_transform(Xs[1]))


# -------------------------------------------------- resolve planning ----

@pytest.mark.parametrize("metric", CONDITIONED_METRICS)
def test_resolve_auto_thresholds_on_kappa(metric):
    near = _near_origin()
    Xo, rep = resolve(near, metric=metric)
    assert (rep.form, rep.conditioned) == ("gram", False)
    assert Xo is not near or Xo.dtype == np.float32  # unchanged f32 pass
    np.testing.assert_array_equal(Xo, near)
    Xc, repc = resolve(near + 1.0e4, metric=metric)
    assert (repc.form, repc.conditioned) == ("direct", True)
    assert repc.kappa > KAPPA_SAFE
    assert float(np.max(np.abs(Xc))) < 2.0


def test_resolve_fast_and_safe_modes():
    X = _near_origin() + 1.0e4
    _, fast = resolve(X, metric="euclidean", policy="fast")
    assert (fast.form, fast.conditioned) == ("gram", False)
    Xs_, safe = resolve(_near_origin(), metric="euclidean", policy="safe")
    assert (safe.form, safe.conditioned) == ("direct", True)


def test_resolve_cosine_never_conditions():
    """Centering is not an isometry of cosine — even safe mode must
    pass the coordinates through untouched."""
    X = _near_origin() + 1.0e4
    for policy in ("fast", "auto", "safe"):
        Xo, rep = resolve(X, metric="cosine", policy=policy)
        assert (rep.form, rep.conditioned) == ("gram", False)
        np.testing.assert_array_equal(Xo, X)


def test_resolve_batched_shape_guard():
    with pytest.raises(ValueError, match="batched"):
        resolve(_near_origin(), metric="euclidean", batched=True)
    Xs = np.stack([_near_origin(seed=3), _near_origin(seed=4) + 1e4])
    Xo, rep = resolve(Xs, metric="euclidean", batched=True)
    assert rep.conditioned and Xo.shape == Xs.shape


# ------------------------------------------------------ bf16 storage ----

def test_quantize_bf16_is_storage_rounding():
    rng = np.random.default_rng(5)
    X = rng.normal(size=(64, 4)).astype(np.float32)
    Q = _quantize_bf16(X)
    assert Q.dtype == np.float32 and Q.shape == X.shape
    # every value sits on the bf16 lattice (low 16 mantissa bits clear)
    assert not np.any(Q.view(np.uint32) & 0xFFFF)
    # round-to-nearest: relative error within one bf16 ulp
    np.testing.assert_allclose(Q, X, rtol=2.0 ** -8)
    np.testing.assert_array_equal(_quantize_bf16(Q), Q)  # idempotent


def test_resolve_bf16_certifies_on_conditioned_grid():
    X = grid_clusters()
    Xo, rep = resolve(X, metric="euclidean",
                      policy=NumericsPolicy(dtype="bf16"))
    assert rep.dtype == "bf16" and rep.fallbacks == 0
    assert rep.conditioned           # the grid sits at offset 1000
    assert not np.any(Xo.view(np.uint32) & 0xFFFF)


def test_resolve_bf16_counted_fallback_on_wide_data():
    """mixed_scale under auto sits below KAPPA_SAFE (no conditioning),
    but its raw κ is far above KAPPA_BF16: the bf16 request degrades to
    f32 with fallbacks=1 — never silently."""
    X = adversarial("mixed_scale")
    stats = condition_stats(X)
    assert KAPPA_BF16 < stats.kappa < KAPPA_SAFE
    Xo, rep = resolve(X, metric="euclidean",
                      policy=NumericsPolicy(dtype="bf16"))
    assert not rep.conditioned
    assert rep.dtype == "f32" and rep.fallbacks == 1


def test_resolve_bf16_fault_trip():
    """The chaos seam: kernels.numerics_trip fails certification on
    demand, producing the same counted degradation."""
    X = grid_clusters()
    with faults.injected("kernels.numerics_trip"):
        _, rep = resolve(X, metric="euclidean",
                         policy=NumericsPolicy(dtype="bf16"))
    assert rep.dtype == "f32" and rep.fallbacks == 1
    _, clean = resolve(X, metric="euclidean",
                       policy=NumericsPolicy(dtype="bf16"))
    assert clean.dtype == "bf16" and clean.fallbacks == 0


# ------------------------------------------------ facade integration ----

def test_fit_stamps_numerics_report():
    fv = FastVAT().fit(_near_origin())
    rep = fv.result.meta.numerics
    assert isinstance(rep, NumericsReport)
    assert (rep.mode, rep.form, rep.conditioned) == ("auto", "gram", False)
    far = FastVAT().fit(_near_origin() + 1.0e4)
    assert far.result.meta.numerics.form == "direct"


def test_precomputed_and_memmap_bypass_the_prepass(tmp_path):
    import jax.numpy as jnp
    from repro.kernels import ops as kops
    X = _near_origin(n=48)
    D = np.asarray(kops.pairwise_dist(jnp.asarray(X)))
    via = FastVAT(metric="precomputed").fit(D)
    assert via.result.meta.numerics is None
    mm_path = tmp_path / "pts.f32"
    mm = np.memmap(mm_path, dtype=np.float32, mode="w+", shape=X.shape)
    mm[:] = X + 1.0e4                 # ill-conditioned, but out-of-core
    mm.flush()
    via_mm = FastVAT(method="vat").fit(mm)
    assert via_mm.result.meta.numerics is None


def test_fit_many_stamps_worst_lane_report():
    Xs = np.stack([_near_origin(seed=7), _near_origin(seed=8) + 1.0e4])
    fv = FastVAT(method="ivat").fit_many(Xs)
    rep = fv.result.meta.numerics
    assert rep.conditioned and rep.form == "direct"
    assert rep.kappa > KAPPA_SAFE


# ------------------------------------- the shift-invariance acceptance ----

SHIFTS = (1e3, 1e4, 1e6, -1e6)
SOLO_CONFIGS = (
    ("vat", {}),
    ("ivat", {}),
    ("flashvat", {"sample_size": 32}),                  # turbo engine
    ("flashvat", {"sample_size": 32, "turbo": False}),  # stepwise engine
    ("approx", {"knn_k": 8}),
)


@pytest.mark.parametrize("metric", CONDITIONED_METRICS)
@pytest.mark.parametrize("method,kw", SOLO_CONFIGS,
                         ids=["vat", "ivat", "flashvat", "turbo-off",
                              "approx"])
def test_orderings_shift_invariant_bitwise_solo(metric, method, kw):
    """ISSUE 10 acceptance: under the default auto policy,
    ``fit(X + c·1)`` reproduces ``fit(X)``'s ordering BITWISE for |c|
    up to 1e6 — every translation-invariant metric, every rung."""
    X = grid_clusters()
    base = FastVAT(method=method, metric=metric, **kw).fit(X)
    assert base.result.meta.numerics.conditioned   # κ(X) > KAPPA_SAFE
    for c in SHIFTS:
        shifted = FastVAT(method=method, metric=metric, **kw).fit(
            X + np.float32(c))
        rep = shifted.result.meta.numerics
        assert rep.conditioned and rep.form == "direct"
        np.testing.assert_array_equal(shifted.order(), base.order(),
                                      err_msg=f"c={c}")


@pytest.mark.parametrize("method,kw", SOLO_CONFIGS[:3],
                         ids=["vat", "ivat", "flashvat"])
def test_orderings_shift_invariant_bitwise_batched(method, kw):
    Xs = np.stack([grid_clusters(seed=0), grid_clusters(seed=1)])
    base = FastVAT(method=method, metric="sqeuclidean", **kw).fit_many(Xs)
    for c in (1e3, -1e6):
        shifted = FastVAT(method=method, metric="sqeuclidean",
                          **kw).fit_many(Xs + np.float32(c))
        np.testing.assert_array_equal(shifted.order(), base.order(),
                                      err_msg=f"c={c}")


def test_fast_mode_is_the_preshield_path():
    """numerics='fast' must leave the data untouched — byte-for-byte
    the pre-shield Gram behavior, even on hostile offsets."""
    X = grid_clusters()
    fv = FastVAT(numerics="fast").fit(X + np.float32(1e4))
    rep = fv.result.meta.numerics
    assert (rep.form, rep.conditioned) == ("gram", False)


# ------------------------------------------------- zero-norm admission ----

def _with_zero_row(n=32, d=4, seed=11):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d)).astype(np.float32)
    X[n // 2] = 0.0
    return X


def test_cosine_zero_norm_rejected_solo():
    X = _with_zero_row()
    with pytest.raises(InvalidInput, match="zero-norm") as ei:
        FastVAT(metric="cosine").fit(X)
    assert ei.value.reason == "zero_norm"
    # other metrics are perfectly happy with the origin as a point
    FastVAT(metric="euclidean").fit(X)
    # and the escape hatch keeps the documented eps-guard semantics
    fv = FastVAT(metric="cosine", validate=False).fit(X)
    assert len(fv.order()) == 32


def test_cosine_zero_norm_rejected_batched():
    Xs = np.stack([_with_zero_row(seed=12), _with_zero_row(seed=13)])
    Xs[0, 16] = 1.0                       # lane 1 carries the zero row
    with pytest.raises(InvalidInput) as ei:
        FastVAT(metric="cosine").fit_many(Xs)
    assert ei.value.reason == "zero_norm"
    FastVAT(metric="cosine", validate=False).fit_many(Xs)


def test_cosine_zero_norm_rejected_streaming():
    sv = StreamingVAT(cap=16, d=4, metric="cosine")
    sv.update(np.abs(_with_zero_row(seed=14)[:8]) + 0.1)
    n_before = len(sv.pts)
    chunk = _with_zero_row(n=8, seed=15)
    with pytest.raises(InvalidInput) as ei:
        sv.update(chunk)
    assert ei.value.reason == "zero_norm"
    assert len(sv.pts) == n_before        # whole chunk refused atomically
    relaxed = StreamingVAT(cap=16, d=4, metric="cosine", validate=False)
    relaxed.update(chunk)
    assert relaxed.n_seen == 8


# ------------------------------------- admission across chunk edges ----

def _cosine_table(n, d=768, seed=21):
    return np.random.default_rng(seed).normal(size=(n, d)).astype(np.float32)


@pytest.mark.parametrize("rows", [
    [_chunk_rows(768) - 1], [_chunk_rows(768)],
    [_chunk_rows(768) - 1, _chunk_rows(768)]],
    ids=["last-of-chunk", "first-of-next", "both"])
def test_zero_norm_row_at_a_chunk_edge(rows):
    X = _cosine_table(3 * _chunk_rows(768) + 7)
    X[rows] = 0.0
    with pytest.raises(InvalidInput,
                       match=re.escape(f"(row(s) {rows})")) as ei:
        validate_points(X, metric="cosine")
    assert ei.value.reason == "zero_norm"


def test_zero_norm_subnormal_row_accepted_negative_zero_rejected():
    X = _cosine_table(2 * _chunk_rows(768) + 3)
    X[5] = np.float32(1e-45)              # the smallest f32 subnormal
    validate_points(X, metric="cosine")
    X[-1] = -0.0
    with pytest.raises(InvalidInput, match=re.escape(
            f"(row(s) {[X.shape[0] - 1]})")) as ei:
        validate_points(X, metric="cosine")
    assert ei.value.reason == "zero_norm"


def test_zero_norm_f64_row_that_squares_to_zero_rejected():
    """Wider floats keep the f64 norms: a row whose squares underflow
    reads zero-norm, as the whole-array f64 screen had it."""
    X = np.random.default_rng(22).normal(size=(40, 5))
    X[9] = 1e-200
    with pytest.raises(InvalidInput, match=re.escape("(row(s) [9])")):
        validate_points(X, metric="cosine")


def test_nan_in_the_final_partial_chunk():
    X = _cosine_table(2 * _chunk_rows(768) + 5)
    X[-1, 700] = np.nan
    with pytest.raises(InvalidInput) as ei:
        validate_points(X, metric="cosine")
    assert ei.value.reason == "non_finite"


def test_zero_norm_batched_names_the_second_lane():
    Xs = np.stack([_cosine_table(_chunk_rows(768) + 9, seed=s)
                   for s in (23, 24, 25)])
    Xs[1, _chunk_rows(768) + 4] = 0.0
    with pytest.raises(InvalidInput,
                       match=re.escape("(lane(s) [1])")) as ei:
        validate_points(Xs, batched=True, metric="cosine")
    assert ei.value.reason == "zero_norm"


def test_int8_full_range_column_is_not_degenerate():
    """Degeneracy compares rows instead of subtracting column extremes:
    np.ptp wraps an int8 column spanning -128..127 to -1."""
    X = np.zeros((8, 2), np.int8)
    X[::2, 0], X[1::2, 0] = -128, 127
    validate_points(X)
    Xs = np.stack([X, np.full((8, 2), 3, np.int8)])
    with pytest.raises(InvalidInput,
                       match=re.escape("lane(s) [1]")) as ei:
        validate_points(Xs, batched=True)
    assert ei.value.reason == "degenerate"


# -------------------------------------------- adversarial properties ----

@settings(max_examples=5, deadline=None)
@given(name=st.sampled_from(ADVERSARIAL_NAMES),
       metric=st.sampled_from(CONDITIONED_METRICS))
def test_auto_policy_certifies_on_adversarial_data(name, metric):
    """Property sweep over the shared worst-case pool: a vat fit under
    the default auto policy always meets its certification bound."""
    X = adversarial(name, n=48)
    r = certify_fit(X, method="vat", metric=metric, generator=name)
    assert r.ok, r


def test_fast_mode_actually_fails_on_the_adversary():
    """The shield is load-bearing: the SAME data that certifies under
    auto breaks its bound when conditioning is forced off."""
    X = adversarial("tiny_gaps", n=48)
    r_auto = certify_fit(X, method="vat", metric="sqeuclidean",
                         policy="auto")
    r_fast = certify_fit(X, method="vat", metric="sqeuclidean",
                         policy="fast")
    assert r_auto.ok and r_auto.conditioned
    assert not r_fast.ok and r_fast.excess > r_auto.excess


# ----------------------------------------------- certification harness ----

def test_oracle_excess_of_the_oracle_is_zero():
    X = _near_origin(n=24)
    from repro.core.naive import vat_order_naive
    order = vat_order_naive(oracle_dissim(X, "euclidean").tolist())
    excess, exact = ordering_excess(X, order, "euclidean")
    assert excess == 0.0 and exact


def test_certify_smoke_sweep_passes():
    results = sweep(methods=("vat",), metrics=("euclidean",),
                    generators=None, n=32)
    assert len(results) == 5 * 3          # 5 generators x 3 policies
    assert all(r.ok for r in results), [r for r in results if not r.ok]
    # determinism: the same seed reproduces the same cells exactly
    again = sweep(methods=("vat",), metrics=("euclidean",), n=32)
    assert results == again


# ----------------------------------------------------- serving layer ----

def test_program_key_carries_the_resolved_plan():
    cfg = ServeConfig()
    kg = resolve_key(100, 4, method="vat", config=cfg)
    kd = resolve_key(100, 4, method="vat", config=cfg, num_form="direct")
    kb = resolve_key(100, 4, method="vat", config=cfg, num_dtype="bf16")
    assert len({kg, kd, kb}) == 3         # no cross-plan coalescing
    assert (kg.num_form, kg.num_dtype) == ("gram", "f32")


def test_serve_resolves_per_request_and_matches_solo():
    X = grid_clusters()
    with TendencyServer(ServeConfig(window_s=0.001)) as srv:
        near = srv.fit(_near_origin())
        far = srv.fit(X + np.float32(1e4))
    assert near.meta.numerics.form == "gram"
    rep = far.meta.numerics
    assert rep.conditioned and rep.form == "direct"
    solo = FastVAT(method="vat").fit(X + np.float32(1e4))
    np.testing.assert_array_equal(np.asarray(far.order), solo.order())


def test_serve_bf16_fallback_is_counted():
    cfg = ServeConfig(window_s=0.001,
                      numerics=NumericsPolicy(dtype="bf16"))
    X = grid_clusters()
    with TendencyServer(cfg) as srv:
        clean = srv.fit(X)
        assert clean.meta.numerics.dtype == "bf16"
        assert srv.stats().resilience.numerics_fallbacks == 0
        with faults.injected("kernels.numerics_trip"):
            tripped = srv.fit(X + np.float32(4096.0))
        assert tripped.meta.numerics.dtype == "f32"
        assert tripped.meta.numerics.fallbacks == 1
        assert srv.stats().resilience.numerics_fallbacks == 1
