"""The approx rung's API seams: auto-selection boundaries (monkeypatched
thresholds, like test_turbo.py's VMEM-seam tests), capability flags, the
error report riding on ``ResultMeta``, and the memory story — a dispatch
census pinning the kNN kernel to Pallas calls plus the no-(n,n) tripwire
mirror from test_bigvat.py."""
import dataclasses
import functools
from collections import Counter

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import core
from repro.api import FastVAT, registry as reg, select_method
from repro.api.registry import MEDIUM_N
from repro.core import approx_mst
from repro.core.approx_mst import ApproxStats, knn_graph_anchored
from repro.kernels import ops as kops
from repro.kernels.ref import pairwise_dissim_ref


def _blobs(n, k=3, d=2, seed=0, sep=40.0):
    rng = np.random.default_rng(seed)
    centers = (sep * rng.normal(size=(k, d))).astype(np.float32)
    lab = rng.integers(0, k, size=n)
    X = centers[lab] + rng.normal(scale=1.0, size=(n, d)).astype(np.float32)
    return X.astype(np.float32), lab.astype(np.int32)


def _lower_threshold(monkeypatch, name: str, threshold: float):
    """Re-register `name` with a test threshold, restored on teardown."""
    monkeypatch.setitem(
        reg._REGISTRY, name,
        dataclasses.replace(reg.get_rung(name), auto_threshold=threshold))


# ------------------------------------------------ selection seams ----

def test_exact_to_approx_boundary_flips_at_threshold(monkeypatch):
    """±1 around the flashvat ceiling flips the auto route to approx —
    exercised at a test-sized threshold so the fixture stays tiny."""
    assert select_method(MEDIUM_N) == "flashvat"
    assert select_method(MEDIUM_N + 1) == "approx"
    _lower_threshold(monkeypatch, "vat", 50)
    _lower_threshold(monkeypatch, "flashvat", 100)
    assert select_method(100) == "flashvat"
    assert select_method(101) == "approx"
    assert select_method(10**9) == "approx"    # the unbounded fallback


def test_auto_fit_routes_approx_past_threshold(monkeypatch):
    """A fit just past the (lowered) exact ceiling resolves approx
    end-to-end: banded image, spanning order, stats on meta."""
    _lower_threshold(monkeypatch, "vat", 50)
    _lower_threshold(monkeypatch, "flashvat", 100)
    X, lab = _blobs(300, k=3, seed=2)
    fv = FastVAT(sample_size=32, knn_k=8).fit(X)
    assert fv.method_resolved == "approx"
    assert sorted(fv.order().tolist()) == list(range(300))
    assert fv.image(resolution=64).shape == (64, 64)
    s = fv.result.meta.approx
    assert isinstance(s, ApproxStats) and s.k == 8
    rep = fv.assess()
    assert rep["method"] == "approx" and rep["k_est"] == 3


def test_auto_fit_routes_exact_at_threshold(monkeypatch):
    _lower_threshold(monkeypatch, "vat", 50)
    _lower_threshold(monkeypatch, "flashvat", 100)
    X, _ = _blobs(100, k=3, seed=2)
    fv = FastVAT(sample_size=32).fit(X)
    assert fv.method_resolved == "flashvat"
    assert fv.result.meta.approx is None       # exact rungs report none


# ---------------------------------------------- capability flags ----

def test_approx_rung_capabilities():
    rung = reg.get_rung("approx")
    assert rung.auto_threshold == float("inf")
    assert not rung.supports_precomputed       # needs points, not a matrix
    assert not rung.supports_batch
    assert reg.get_rung("bigvat").auto_threshold is None   # demoted: opt-in
    assert "approx" in reg.methods()


def test_approx_rejects_precomputed():
    D = np.zeros((8, 8), np.float32)
    with pytest.raises(ValueError, match="precomputed"):
        FastVAT(method="approx", metric="precomputed").fit(D)


def test_precomputed_auto_still_falls_back_exact():
    """Huge-n precomputed input keeps routing to the exact rung — the
    (n, n) matrix already exists, so approx has nothing to save."""
    assert select_method(10**9, precomputed=True) == "vat"


def test_explicit_bigvat_still_available():
    X, _ = _blobs(400, k=2, seed=3)
    fv = FastVAT(method="bigvat", sample_size=32).fit(X)
    assert fv.method_resolved == "bigvat"


# -------------------------------------------- meta / pytree seams ----

def test_approx_stats_meta_stays_valid_pytree_aux():
    """ApproxStats is frozen + hashable, so a TendencyResult carrying it
    survives flatten/unflatten (meta is static aux data)."""
    X, _ = _blobs(200, k=2, seed=4)
    res = FastVAT(method="approx", knn_k=6, sample_size=16).fit(X).result
    assert hash(res.meta) == hash(res.meta)
    leaves, treedef = jax.tree_util.tree_flatten(res)
    back = jax.tree_util.tree_unflatten(treedef, leaves)
    assert back.meta.approx == res.meta.approx


# ------------------------------------------------ dispatch census ----

def _iter_avals(jaxpr):
    """Every intermediate abstract value a jaxpr (and its subjaxprs) binds."""
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            if hasattr(v.aval, "shape"):
                yield v.aval
        for p in eqn.params.values():
            for u in (p if isinstance(p, (list, tuple)) else (p,)):
                sub = getattr(u, "jaxpr", u)
                if hasattr(sub, "eqns"):
                    yield from _iter_avals(sub)


def test_knn_kernel_census_pallas_no_nxn_no_f64():
    """The dispatch pin: the Pallas kNN path holds >= 1 pallas_call in
    its jaxpr while the blocked XLA path holds none — and NEITHER ever
    binds an (n, n)-sized intermediate nor any float64 array (the
    memory contract the million-point rung rests on)."""
    n, k, block = 600, 8, 128
    X = jnp.asarray(np.random.default_rng(0).normal(
        size=(n, 4)).astype(np.float32))
    pal = kops.kernel_dispatch_stats(
        lambda A: kops.knn_graph(A, k=k, use_pallas=True, block=block), X)
    xla = kops.kernel_dispatch_stats(
        lambda A: kops.knn_graph(A, k=k, use_pallas=False, block=block), X)
    assert pal["pallas_calls"] >= 1, pal
    assert xla["pallas_calls"] == 0, xla
    for use_pallas in (True, False):
        jaxpr = jax.make_jaxpr(
            lambda A: kops.knn_graph(A, k=k, use_pallas=use_pallas,
                                     block=block))(X).jaxpr
        avals = list(_iter_avals(jaxpr))
        biggest = max(int(np.prod(a.shape, dtype=int)) for a in avals)
        assert biggest < n * n / 4, (use_pallas, biggest)
        assert not any(a.dtype == np.float64 for a in avals), use_pallas


# ------------------------------------------------ no-(n,n) tripwire ----

def test_anchored_knn_never_materializes_nxn(monkeypatch):
    """Tripwire mirror of test_bigvat: every distance tile the anchored
    assignment pass produces is (assign_block, anchors) at most —
    nothing O(n^2) — and the pass IS tripwire-visible (it goes through
    kernels.ops.pairwise_dist, not around it)."""
    n, k, ab = 5_000, 6, 1_024
    X, _ = _blobs(n, k=3, seed=5)
    shapes = []
    real = kops.pairwise_dist

    def recording(Xa, Ya=None, **kw):
        out = real(Xa, Ya, **kw)
        shapes.append(tuple(out.shape))
        return out

    monkeypatch.setattr(kops, "pairwise_dist", recording)
    dist, idx = knn_graph_anchored(X, k=k, assign_block=ab)
    assert dist.shape == (n, k) and idx.shape == (n, k)
    assert dist.dtype == np.float32            # never an (n, k) float64
    assert shapes, "anchored pass never went through kernels.ops.pairwise_dist"
    assert all(r <= ab and c < n for r, c in shapes), shapes
    # and the graph it built is usable: mostly-filled valid slots
    valid = np.isfinite(dist) & (idx >= 0)
    assert valid.mean() > 0.95


def test_approx_fit_path_never_materializes_nxn(monkeypatch):
    """End-to-end tripwire on the registry fit: every pairwise_dist call
    the whole approx fit makes (band rendering included) stays far below
    (n, n)."""
    n = 2_000
    X, _ = _blobs(n, k=3, seed=6)
    shapes = []
    real = kops.pairwise_dist

    def recording(Xa, Ya=None, **kw):
        out = real(Xa, Ya, **kw)
        shapes.append(tuple(out.shape))
        return out

    monkeypatch.setattr(kops, "pairwise_dist", recording)
    fv = FastVAT(method="approx", sample_size=64, knn_k=8).fit(X)
    assert fv.method_resolved == "approx"
    assert all(r * c <= n * 64 for r, c in shapes), shapes


# -------------------------------------------- demo acceptance test ----

def test_approx_demo_acceptance(monkeypatch):
    """examples/approx_demo.py shrunk to test size: end-to-end through
    the demo's own run(), with the memory pins — every pairwise_dist
    tile far below (n, n), int32 ordering out, working set a small
    fraction of the dense matrix."""
    import importlib.util
    import os
    path = os.path.join(os.path.dirname(__file__), "..", "examples",
                        "approx_demo.py")
    spec = importlib.util.spec_from_file_location("approx_demo", path)
    demo = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(demo)

    shapes = []
    real = kops.pairwise_dist

    def recording(Xa, Ya=None, **kw):
        out = real(Xa, Ya, **kw)
        shapes.append(tuple(out.shape))
        return out

    monkeypatch.setattr(kops, "pairwise_dist", recording)
    n = 1_500
    info = demo.run(n=n, k=6, sample_size=32)
    assert info["method"] == "approx"
    assert sorted(info["order"].tolist()) == list(range(n))
    assert info["order"].dtype == np.int32
    assert info["runs"] == 5                   # 5 generated blobs
    assert info["stats"].k == 6
    assert all(r * c <= n * 64 for r, c in shapes), shapes
    assert info["working_bytes"] * 20 < info["dense_bytes"]


# ----------------------------------- anchored kNN: the cell loop ----

@functools.partial(jax.jit, static_argnames=("metric", "kk"))
def _one_cell_topk(Xq, Xc, qid, cid, *, metric, kk):
    """One (padded) anchor cell's top-kk, from host-gathered rows: the
    per-cell program the batched ``_cell_topk`` replaced."""
    D = pairwise_dissim_ref(Xq, Xc, metric=metric)
    bad = (cid[None, :] < 0) | (cid[None, :] == qid[:, None])
    neg, p = jax.lax.top_k(-jnp.where(bad, jnp.inf, D), kk)
    return -neg, jnp.take(cid, p)


def _anchored_reference(X, *, k, metric, probes, assign_block):
    """The anchored kNN as a host loop over cells: host blocks for the
    assignment, then per cell a host gather of its rows, one
    ``_one_cell_topk`` call and a read.  Returns (dist, idx) and the
    counts of cells without candidates and of cells with no queries."""
    Xn = np.asarray(X, np.float32)
    n, d = Xn.shape
    c = max(32, int(round(np.sqrt(n))))
    aidx = np.random.default_rng(0).choice(n, size=c, replace=False)
    A = jnp.asarray(Xn[aidx])
    probe_idx = np.empty((n, probes), np.int32)
    for s0 in range(0, n, assign_block):
        xb = Xn[s0:s0 + assign_block]
        rows = xb.shape[0]
        if rows < assign_block:
            xb = np.vstack([xb, np.zeros((assign_block - rows, d),
                                         np.float32)])
        D = kops.pairwise_dist(jnp.asarray(xb), A, metric=metric)
        _, pid = jax.lax.top_k(-D, probes)
        probe_idx[s0:s0 + rows] = np.asarray(pid, np.int32)[:rows]

    primary = probe_idx[:, 0]
    by_cell = np.argsort(primary, kind="stable")
    start = np.concatenate([[0],
                            np.cumsum(np.bincount(primary, minlength=c))])
    q_order = [np.argsort(probe_idx[:, s], kind="stable")
               for s in range(probes)]
    q_start = [np.concatenate(
        [[0], np.cumsum(np.bincount(probe_idx[:, s], minlength=c))])
        for s in range(probes)]

    part_d = np.full((n, probes, k), np.inf, np.float32)
    part_i = np.full((n, probes, k), -1, np.int64)
    no_cand = no_query = 0
    for g in range(c):
        cand = by_cell[start[g]:start[g + 1]]
        qs = [q_order[s][q_start[s][g]:q_start[s][g + 1]]
              for s in range(probes)]
        slot = np.concatenate(
            [np.full(x.size, s, np.int64) for s, x in enumerate(qs)])
        q = np.concatenate(qs)
        no_cand += cand.size == 0
        no_query += q.size == 0
        if cand.size == 0 or q.size == 0:
            continue
        qp, cp = approx_mst._bucket(q.size), approx_mst._bucket(cand.size)
        Xq = np.zeros((qp, d), np.float32)
        Xq[:q.size] = Xn[q]
        Xc = np.zeros((cp, d), np.float32)
        Xc[:cand.size] = Xn[cand]
        qid = np.full(qp, -2, np.int32)
        qid[:q.size] = q
        cid = np.full(cp, -1, np.int32)
        cid[:cand.size] = cand
        kk = min(k, cp)
        gd, gi = _one_cell_topk(jnp.asarray(Xq), jnp.asarray(Xc),
                                jnp.asarray(qid), jnp.asarray(cid),
                                metric=metric, kk=kk)
        gd = np.asarray(gd, np.float32)[:q.size]
        gi = np.asarray(gi, np.int64)[:q.size]
        part_d[q, slot, :kk] = gd
        part_i[q, slot, :kk] = np.where(np.isfinite(gd), gi, -1)

    flat_d = part_d.reshape(n, probes * k)
    flat_i = part_i.reshape(n, probes * k)
    sel = np.argsort(flat_d, axis=1, kind="stable")[:, :k]
    return (np.take_along_axis(flat_d, sel, axis=1),
            np.take_along_axis(flat_i, sel, axis=1), no_cand, no_query)


def _with_twins(n, seed):
    """Blobs whose first quarter is one repeated point: the anchors drawn
    among the copies tie, and all but the first get no primary member."""
    X, _ = _blobs(n, k=3, d=5, seed=seed, sep=8.0)
    X[:n // 4] = X[0]
    return X


@pytest.mark.parametrize("on_device", [False, True], ids=["host", "device"])
@pytest.mark.parametrize("probes", [1, 2, 3])
@pytest.mark.parametrize("metric",
                         ["euclidean", "sqeuclidean", "cosine", "manhattan"])
def test_anchored_cells_on_device_equal_the_per_cell_loop(
        monkeypatch, metric, probes, on_device):
    """The batched, device-resident cell stage returns the host loop's
    (dist, idx) bit for bit.  A small dispatch budget makes several
    chunks per bucket pair and a padded last chunk; the repeated point
    makes cells with no candidates (and, at one probe, no queries)."""
    n, k, ab = 1_200, 6, 512
    X = _with_twins(n, seed=10 + probes)
    want_d, want_i, no_cand, no_query = _anchored_reference(
        X, k=k, metric=metric, probes=probes, assign_block=ab)
    assert no_cand > 0
    assert no_query > 0 if probes == 1 else no_cand > no_query

    chunks = Counter()
    real = approx_mst._cell_topk

    def recording(X, qid, slot, cid, cells, *args, **kw):
        chunks[(qid.shape[1], cid.shape[1])] += 1
        padded.append(int(cells) < qid.shape[0])
        return real(X, qid, slot, cid, cells, *args, **kw)

    padded = []
    monkeypatch.setattr(approx_mst, "_cell_topk", recording)
    monkeypatch.setattr(approx_mst, "_CELL_DISPATCH_BYTES", 48 << 10)
    got_d, got_i = knn_graph_anchored(jnp.asarray(X) if on_device else X,
                                      k=k, metric=metric, probes=probes,
                                      assign_block=ab)
    assert len(chunks) > 1 and max(chunks.values()) > 1 and any(padded)
    assert got_d.dtype == np.float32 and got_i.dtype == np.int64
    np.testing.assert_array_equal(got_d, want_d)
    np.testing.assert_array_equal(got_i, want_i)


def test_anchored_cells_compile_once_per_bucket_pair(monkeypatch):
    """One ``_cell_topk`` program per (query, candidate) bucket pair over
    three tables, and a second fit of the same table compiles and traces
    nothing (what the benchmark's window checks)."""
    pairs = set()
    real = approx_mst._cell_topk

    def recording(X, qid, slot, cid, *args, **kw):
        pairs.add((qid.shape[1], cid.shape[1]))
        return real(X, qid, slot, cid, *args, **kw)

    monkeypatch.setattr(approx_mst, "_cell_topk", recording)
    real.clear_cache()
    tables = [jnp.asarray(_blobs(2_000, k=4, d=6, seed=s)[0])
              for s in (21, 22, 23)]
    for X in tables:
        core.approx_vat(X, k=8, knn_mode="anchored")
    assert 0 < real._cache_size() <= len(pairs)

    events = []

    def on_event(event, duration, **kw):
        if event.startswith("/jax/core/compile/"):
            events.append(event)

    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        core.approx_vat(tables[-1], k=8, knn_mode="anchored")
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
    assert events == []
