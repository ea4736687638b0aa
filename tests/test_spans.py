"""The program's span recorder (repro.spans) and the spans its stages
record."""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap
import threading
from collections import Counter

import numpy as np
import pytest

import jax.numpy as jnp

from repro import FastVAT, spans
from repro.core import approx_mst


@pytest.fixture(autouse=True)
def _empty_ring():
    spans.clear()
    yield
    spans.clear()


def by_name(records):
    return {r.name: r for r in records}


def test_parent_root_and_thread_links():
    with spans.span("a"):
        with spans.span("b"):
            with spans.span("c"):
                pass
        with spans.span("d"):
            pass
    got = by_name(spans.recent())
    a, b, c, d = (got[k] for k in "abcd")
    assert a.parent is None and a.root == a.id
    assert b.parent == a.id and c.parent == b.id and d.parent == a.id
    assert {b.root, c.root, d.root} == {a.id}
    assert {r.thread for r in got.values()} == {threading.get_ident()}
    assert [r.name for r in spans.recent()] == ["c", "b", "d", "a"]
    assert [r.seq for r in spans.recent()] == sorted(
        r.seq for r in spans.recent())
    assert a.start_ns <= b.start_ns <= c.start_ns <= c.end_ns <= b.end_ns \
        <= d.start_ns <= d.end_ns <= a.end_ns


def test_two_threads_keep_their_own_stacks():
    both_open = threading.Barrier(2, timeout=10)
    idents = {}

    def work(tag):
        idents[tag] = threading.get_ident()
        with spans.span(f"root.{tag}"):
            with spans.span(f"mid.{tag}"):
                both_open.wait()
                with spans.span(f"leaf.{tag}"):
                    pass

    threads = [threading.Thread(target=work, args=(t,)) for t in "xy"]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=10)
        assert not t.is_alive()
    got = by_name(spans.recent())
    assert len(got) == 6
    for tag in "xy":
        root, mid, leaf = (got[f"{k}.{tag}"] for k in ("root", "mid", "leaf"))
        assert root.parent is None
        assert mid.parent == root.id and leaf.parent == mid.id
        assert mid.root == leaf.root == root.id
        assert {root.thread, mid.thread, leaf.thread} == {idents[tag]}
    assert idents["x"] != idents["y"]


def test_a_span_closes_when_its_body_raises():
    with pytest.raises(KeyError):
        with spans.span("outer"):
            with spans.span("failing"):
                raise KeyError("boom")
    got = by_name(spans.recent())
    assert set(got) == {"outer", "failing"}
    assert got["failing"].parent == got["outer"].id
    with spans.span("after"):           # the thread's stack is empty again
        pass
    assert by_name(spans.recent())["after"].parent is None


def test_decorated_functions_record_every_call_and_keep_their_name():
    @spans.span("stage")
    def stage(x):
        """Doc."""
        return x + 1

    assert stage(1) == 2 and stage(2) == 3
    assert stage.__name__ == "stage" and stage.__doc__ == "Doc."
    assert [r.name for r in spans.recent()] == ["stage", "stage"]


def test_the_ring_is_bounded_and_counts_what_it_drops():
    extra = 10
    with spans.span("s") as probe:
        pass
    first = probe._id
    spans.clear()
    for _ in range(spans.RING + extra):
        with spans.span("s"):
            pass
    kept = spans.recent()
    assert len(kept) == spans.RING
    assert spans.dropped() == extra
    # the oldest went: what is kept is the newest RING, in order
    assert [r.seq for r in kept] == list(range(kept[0].seq,
                                              kept[0].seq + spans.RING))
    assert kept[0].id == first + 1 + extra
    spans.clear()
    assert spans.recent() == () and spans.dropped() == 0


@pytest.mark.parametrize("dtype", [None, np.int32, np.float32])
def test_readback_equals_asarray(dtype):
    host = np.arange(12, dtype=np.float32).reshape(3, 4) - 5.5
    dev = jnp.asarray(host)
    for x in (host, dev):
        got = spans.readback(x, dtype)
        want = np.asarray(x, dtype)
        assert isinstance(got, np.ndarray) and got.dtype == want.dtype
        np.testing.assert_array_equal(got, want)
    # only the device array was waited for
    assert [r.name for r in spans.recent()] == ["device.readback"]


def _blobs(n, d, seed=0):
    rng = np.random.default_rng(seed)
    centres = rng.normal(size=(4, d)) * 6
    return (centres[rng.integers(0, 4, n)]
            + rng.normal(size=(n, d))).astype(np.float32)


def test_anchored_approx_records_its_stages_and_every_blocking_read(
        monkeypatch):
    X = _blobs(600, 8)
    calls = Counter()
    cell_topk = approx_mst._cell_topk

    def counted(*args, **kwargs):
        calls["cell"] += 1
        return cell_topk(*args, **kwargs)

    monkeypatch.setattr(approx_mst, "_cell_topk", counted)
    with spans.span("caller"):
        res = approx_mst.approx_vat(jnp.asarray(X), k=5, knn_mode="anchored")
    recs = spans.recent()
    got = by_name(recs)
    caller = got["caller"]
    stages = ("approx.knn", "approx.boruvka", "approx.walk")
    for name in stages:
        assert got[name].parent == caller.id
    assert got["approx.knn"].end_ns <= got["approx.boruvka"].start_ns
    assert got["approx.boruvka"].end_ns <= got["approx.walk"].start_ns
    reads = Counter(next(r.name for r in recs if r.id == rb.parent)
                    for rb in recs if rb.name == "device.readback")
    st = res.stats
    repair = 1 if 1 < st.components <= approx_mst.REPAIR_MAX_C else 0
    # the table; then the assignment, read once for all its blocks, and
    # the two partial lists, read once after every cell call was launched
    assert reads["caller"] == 1
    assert calls["cell"] >= 1
    assert reads["approx.knn"] == 1 + 2
    # four per Borůvka pass that recorded edges, the last pass's flag,
    # the final labels, and the repair's distances when it ran
    assert reads["approx.boruvka"] == 4 * st.n_passes + 2 + repair
    assert "approx.walk" not in reads
    assert all(r.root == caller.id for r in recs)


def test_fit_and_assess_are_the_roots_of_one_assessment():
    X = _blobs(60, 3)
    FastVAT().fit(X).assess()
    recs = spans.recent()
    roots = [r for r in recs if r.parent is None]
    assert [r.name for r in roots] == ["fastvat.fit", "fastvat.assess"]
    fit = roots[0]
    under = [r.name for r in recs if r.parent == fit.id]
    assert under == ["api.admission", "numerics.prepass", "device.upload"]
    assert all(r.root == fit.id for r in recs if r.name != "fastvat.assess")


def test_validate_false_skips_the_admission_span():
    FastVAT(validate=False).fit(_blobs(60, 3))
    names = [r.name for r in spans.recent()]
    assert "api.admission" not in names and "numerics.prepass" in names


def test_flashvat_records_its_engine_and_the_band_render():
    FastVAT(method="flashvat", sample_size=32).fit(_blobs(300, 4))
    recs = spans.recent()
    fit = by_name(recs)["fastvat.fit"]
    under = [r.name for r in recs if r.parent == fit.id]
    assert under == ["api.admission", "numerics.prepass", "device.upload",
                     "exact.solo", "render.band"]


# Four CPU devices; the shard threshold is lowered in the subprocess only,
# so that a small table takes the sharded branch of _flash_order.
SHARDED_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import numpy as np
    from repro import FastVAT, spans
    from repro.api import registry
    registry.FLASH_SHARD_MIN_N = 64
    X = np.random.default_rng(0).normal(size=(100, 4)).astype(np.float32)
    FastVAT(method="flashvat", sample_size=16).fit(X)
    recs = spans.recent()
    fit = [r for r in recs if r.name == "fastvat.fit"][0]
    print("UNDER", ",".join(r.name for r in recs if r.parent == fit.id))
""")


def test_sharded_flashvat_records_exact_sharded():
    r = subprocess.run([sys.executable, "-c", SHARDED_SCRIPT],
                       capture_output=True, text=True, timeout=300,
                       env={"PYTHONPATH": os.path.join(
                           os.path.dirname(__file__), "..", "src"),
                            "PATH": os.environ["PATH"],
                            "JAX_PLATFORMS": "cpu"})
    assert ("UNDER api.admission,numerics.prepass,device.upload,"
            "exact.sharded,render.band") in r.stdout, r.stderr[-2000:]
