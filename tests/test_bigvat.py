"""Big-VAT: oracle agreement with exact VAT, the no-(n,n)-allocation
property of the tiled pass, FastVAT routing, and a regression pin on the
shard_map import fix."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import core
from repro.core.bigvat import bigvat, nearest_prototype_assign, smoothed_image
from repro.api import FastVAT, assess_tendency, select_method, SMALL_N, MEDIUM_N


def _blobs(n, k=3, d=2, seed=0, sep=40.0):
    rng = np.random.default_rng(seed)
    centers = (sep * rng.normal(size=(k, d))).astype(np.float32)
    lab = rng.integers(0, k, size=n)
    X = centers[lab] + rng.normal(scale=1.0, size=(n, d)).astype(np.float32)
    return X.astype(np.float32), lab.astype(np.int32)


# ------------------------------------------------------------ oracle ----

def test_bigvat_k_est_matches_exact_vat():
    """bigvat's sample image yields the same block_structure_score
    k-estimate as exact VAT on the full (n, n) matrix."""
    X, _ = _blobs(600, k=3)
    _, k_exact = core.block_structure_score(core.vat(jnp.asarray(X)).rstar)
    res = bigvat(X, s=64)
    _, k_big = core.block_structure_score(res.sample.vat.rstar)
    assert int(k_big) == int(k_exact) == 3


def test_bigvat_grouping_keeps_clusters_contiguous():
    X, lab = _blobs(2_000, k=4, seed=1)
    res = bigvat(X, s=64)
    order = np.asarray(res.order)
    assert sorted(order.tolist()) == list(range(len(X)))  # permutation
    runs = 1 + int(np.sum(lab[order][1:] != lab[order][:-1]))
    assert runs == 4
    assert int(np.sum(np.asarray(res.group_sizes))) == len(X)


def test_bigvat_smoothed_image_has_block_structure():
    X, _ = _blobs(3_000, k=3, seed=2)
    res = bigvat(X, s=64)
    img = smoothed_image(res, resolution=128)
    assert img.shape == (128, 128)
    score, k = core.block_structure_score(jnp.asarray(img))
    assert float(score) > 0.5


# ---------------------------------------------- no-(n,n) allocation ----

def test_tiled_pass_never_materializes_nxn(monkeypatch):
    """Memory-shape assertion: every distance tile the extension pass
    produces is at most (block, s) — nothing O(n^2), nothing even O(n)."""
    from repro.kernels import ops as kops
    n, s, block = 50_000, 64, 4_096
    X, _ = _blobs(n, k=3, d=2, seed=3)
    P = X[:s]

    shapes = []
    real = kops.pairwise_dist

    def recording(Xa, Ya=None, **kw):
        out = real(Xa, Ya, **kw)
        shapes.append(tuple(out.shape))
        return out

    monkeypatch.setattr(kops, "pairwise_dist", recording)
    labels, dists = nearest_prototype_assign(X, P, block=block)
    assert labels.shape == (n,) and dists.shape == (n,)
    assert shapes, "tiled pass never went through kernels.ops.pairwise_dist"
    assert all(r <= block and c <= s for r, c in shapes), shapes
    # correctness of the tiling: matches a brute-force (chunked) argmin
    ref_lab = np.asarray(jnp.argmin(real(jnp.asarray(X[:1000]), jnp.asarray(P)), axis=1))
    np.testing.assert_array_equal(np.asarray(labels)[:1000], ref_lab)


def test_bigvat_accepts_memmap(tmp_path):
    """Out-of-core input: X as np.memmap streams through the tiled pass."""
    X, _ = _blobs(5_000, k=3, seed=4)
    path = tmp_path / "X.f32"
    mm = np.memmap(path, dtype=np.float32, mode="w+", shape=X.shape)
    mm[:] = X
    mm.flush()
    ro = np.memmap(path, dtype=np.float32, mode="r", shape=X.shape)
    res = bigvat(ro, s=32, block=1024)
    assert sorted(np.asarray(res.order).tolist()) == list(range(len(X)))


# ------------------------------------------------------ FastVAT api ----

def test_select_method_thresholds():
    assert select_method(SMALL_N) == "vat"
    assert select_method(SMALL_N + 1) == "flashvat"
    assert select_method(MEDIUM_N) == "flashvat"
    assert select_method(MEDIUM_N + 1) == "approx"


def test_fastvat_auto_routes_vat():
    X, _ = _blobs(400)
    fv = FastVAT().fit(X)
    assert fv.method_resolved == "vat"
    assert fv.image().shape == (400, 400)
    assert sorted(fv.order().tolist()) == list(range(400))


def test_fastvat_auto_routes_flashvat():
    """The mid-size window now gets *exact* matrix-free VAT, not the
    sampled approximation — the Flash-VAT promotion."""
    X, _ = _blobs(5_000)
    fv = FastVAT(sample_size=64).fit(X)
    assert fv.method_resolved == "flashvat"
    assert sorted(fv.order().tolist()) == list(range(5_000))  # full, exact
    assert fv.image(resolution=128).shape == (128, 128)
    assert len(fv.sample_indices()) == 64


def test_fastvat_explicit_svat_still_works():
    X, _ = _blobs(5_000)
    fv = FastVAT(method="svat", sample_size=64).fit(X)
    assert fv.method_resolved == "svat"
    assert fv.image().shape == (64, 64)
    assert len(fv.sample_indices()) == 64


def test_fastvat_explicit_bigvat_past_flash_window():
    # bigvat is opt-in now (the approx rung owns the auto fallback —
    # ISSUE 6) but the explicit pipeline must keep working just past the
    # flashvat window it used to own.
    n = MEDIUM_N + 1_000
    X, lab = _blobs(n, k=3)
    fv = FastVAT(method="bigvat", sample_size=64, block=8_192).fit(X)
    assert fv.method_resolved == "bigvat"
    assert fv.image(resolution=100).shape == (100, 100)
    order = fv.order()
    assert sorted(order.tolist()) == list(range(n))
    rep = fv.assess()
    assert rep["method"] == "bigvat" and rep["k_est"] == 3
    assert rep["clustered"]


def test_fastvat_explicit_ivat():
    X, _ = _blobs(300)
    fv = FastVAT(method="ivat").fit(X)
    iv = fv.image()
    # geodesic max-min distances never exceed the direct ones
    assert np.all(iv <= fv.image(use_ivat=False) + 1e-4)


def test_fastvat_validation():
    with pytest.raises(ValueError):
        FastVAT(method="nope")
    with pytest.raises(RuntimeError):
        FastVAT().order()  # not fitted
    if jax.device_count() < 2:
        with pytest.raises(RuntimeError):
            FastVAT(method="dvat").fit(_blobs(64)[0])


def test_assess_tendency_oneshot():
    X, _ = _blobs(500, k=2, seed=5)
    rep = assess_tendency(X)
    assert rep["method"] == "vat" and rep["k_est"] == 2 and rep["clustered"]


# -------------------------------------------- shard_map import pin ----

def test_shard_map_import_fix():
    """Regression: repro.core.distributed imports against the installed
    JAX's ``jax.shard_map`` and repro.core re-exports its entry points."""
    import repro.core.distributed as dist
    assert callable(jax.shard_map)
    assert core.dvat is dist.dvat
    assert "vat_matrix_free_sharded" in core.__all__
