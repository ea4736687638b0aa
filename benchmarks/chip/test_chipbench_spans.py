"""The reduction from the program's span records to the span metrics,
on synthetic records and traces, and once on a CPU-traced loop.  No
test here loads the TPU library."""
from __future__ import annotations

import sys
import threading
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
REPO = HERE.parents[1]
sys.path[:0] = [str(HERE), str(REPO / "src")]

import harness  # noqa: E402
import spanreduce as sred  # noqa: E402
import tracereduce as tred  # noqa: E402
from repro import spans  # noqa: E402
from repro.spans import Span  # noqa: E402

T, OTHER = 7, 8
NS = 1_000_000          # synthetic records count in milliseconds of ns


class Book:
    """Builds span records the way the recorder closes them."""

    def __init__(self):
        self.records = []

    def add(self, name, start, end, parent=None, thread=T):
        sid = len(self.records) + 1
        root = sid if parent is None else parent.root
        pid = None if parent is None else parent.id
        rec = Span(name, sid, pid, root, thread, start * NS, end * NS, 0)
        self.records.append(rec)
        return rec

    def closed(self):
        """Records in closing order, numbered by ``seq``."""
        order = sorted(self.records, key=lambda r: (r.end_ns, -r.start_ns))
        return [r._replace(seq=i) for i, r in enumerate(order)]


def assessment(book, t0, *, stages=True, assess=True, thread=T):
    """A fit root at t0 with admission, pre-pass and the approx stages,
    then an assess root."""
    fit = book.add(sred.FIT, t0, t0 + 100, thread=thread)
    if stages:
        book.add("api.admission", t0 + 1, t0 + 6, fit, thread)
        book.add("numerics.prepass", t0 + 6, t0 + 16, fit, thread)
        knn = book.add("approx.knn", t0 + 20, t0 + 60, fit, thread)
        book.add(sred.READBACK, t0 + 30, t0 + 35, knn, thread)
        book.add(sred.READBACK, t0 + 50, t0 + 52, knn, thread)
        bor = book.add("approx.boruvka", t0 + 60, t0 + 80, fit, thread)
        book.add(sred.READBACK, t0 + 70, t0 + 71, bor, thread)
        book.add("approx.walk", t0 + 80, t0 + 95, fit, thread)
    if assess:
        book.add(sred.ASSESS, t0 + 100, t0 + 110, thread=thread)
    return fit


def loop_book(warm=2, window=3):
    book = Book()
    fits = [assessment(book, 200 * i) for i in range(warm + window)]
    assessment(book, 410, thread=OTHER)          # another thread's fit
    return book, fits


# ----------------------------------------------------------- window ----

def test_window_is_the_last_attempted_fits_and_their_assesses():
    book, fits = loop_book()
    recs = book.closed()
    win = sred.window(recs, 3, T)
    assert [r.name for r in win] == [sred.FIT, sred.ASSESS] * 3
    assert [r.id for r in win if r.name == sred.FIT] == \
        [f.id for f in fits[2:]]
    assert all(r.thread == T for r in win)
    assert sred.window(recs, 5, T) is not None        # warm ones count too
    assert sred.window(recs, 6, T) is None            # too few fits kept
    assert sred.window(recs, 0, T) is None


def test_a_failed_attempt_has_no_assess():
    book = Book()
    assessment(book, 0)
    assessment(book, 200, assess=False)
    assessment(book, 400)
    win = sred.window(book.closed(), 2, T)
    assert [r.name for r in win] == [sred.FIT, sred.FIT, sred.ASSESS]


def test_a_ring_that_dropped_window_records_gives_no_window():
    book, fits = loop_book()
    recs = book.closed()
    first = fits[2]
    # evictions that all closed before the window began: whole window
    kept = [r for r in recs if r.end_ns > fits[1].end_ns]
    assert sred.window(kept, 3, T, dropped=len(recs) - len(kept))
    # the oldest kept record closed after the first window fit began:
    # that fit's earliest spans went, so the window is refused
    late = [r for r in recs if r.end_ns > first.start_ns + 10 * NS]
    assert sred.window(late, 3, T, dropped=len(recs) - len(late)) is None
    assert sred.window(late, 3, T) is not None        # nothing dropped


# -------------------------------------------------------- durations ----

def brute_self(span, records, step=NS // 10):
    t = np.arange(span.start_ns, span.end_ns, step) + step / 2
    kids = {}
    for r in records:
        kids.setdefault(r.parent, []).append(r)
    waits, todo = [], [span.id]
    while todo:
        for c in kids.get(todo.pop(), ()):
            todo.append(c.id)
            if c.name == sred.READBACK:
                waits.append(c)
    on = np.zeros_like(t, bool)
    for w in waits:
        on |= (t >= w.start_ns) & (t < w.end_ns)
    return (~on).sum() * step


def test_self_time_matches_a_brute_force_sum():
    book = Book()
    top = book.add("approx.knn", 0, 100)
    book.add(sred.READBACK, 10, 20, top)
    book.add(sred.READBACK, 15, 30, top)              # overlaps the first
    mid = book.add("inner", 40, 70, top)
    book.add(sred.READBACK, 50, 60, mid)              # a grandchild
    book.add(sred.READBACK, 95, 130, top)             # runs past the end
    other = book.add("elsewhere", 200, 300)
    book.add(sred.READBACK, 210, 220, other)          # not under top
    recs = book.closed()
    got = sred.self_ns(top, recs)
    assert got == pytest.approx(brute_self(top, recs), abs=NS // 10)
    assert got == (100 - 20 - 10 - 5) * NS


def test_per_assessment_numbers_of_a_window():
    book, _ = loop_book()
    recs = book.closed()
    got = sred.numbers(recs, sred.window(recs, 3, T))
    assert got["prepass_ms"] == pytest.approx(15.0)
    assert got["knn_host_ms"] == pytest.approx(40.0 - 7.0)
    assert got["boruvka_host_ms"] == pytest.approx(19.0)
    assert got["walk_ms"] == pytest.approx(15.0)
    assert got["readback_ms"] == pytest.approx(8.0)
    assert got["readbacks"] == pytest.approx(3.0)
    assert "idle_unspanned" not in got                # no trace given


# ------------------------------------------------------------ clock ----

DELTA = 1.7e9 + 0.25        # trace seconds = recorder seconds + DELTA


def bench_spans(book, slack=1e-6):
    """The benchmark's spans around each root, on the trace's clock."""
    host = []
    for r in book.records:
        if r.parent is None and r.thread == T:
            label = "bench.fit" if r.name == sred.FIT else "bench.assess"
            host.append((r.start_ns * 1e-9 + DELTA - slack,
                         r.end_ns * 1e-9 + DELTA + slack, label))
    return host


def test_offset_bracket_holds_the_true_offset():
    book, _ = loop_book()
    got = sred.clock_offset(bench_spans(book), book.closed(), T)
    assert got is not None
    lo, hi = got
    assert lo <= DELTA <= hi
    assert hi - lo < 1e-5


def test_offset_pairs_from_the_end_past_earlier_roots():
    book, _ = loop_book()
    host = bench_spans(book)[2:]            # the trace began after warm 1
    lo, hi = sred.clock_offset(host, book.closed(), T)
    assert lo <= DELTA <= hi


def test_no_offset_when_a_bench_span_does_not_hold_its_root():
    book, _ = loop_book()
    host = bench_spans(book)
    s, e, name = host[3]
    host[3] = (s + 0.05, e, name)           # starts after its root did
    assert sred.clock_offset(host, book.closed(), T) is None
    assert sred.clock_offset(host[:0], book.closed(), T) is None


# ------------------------------------------------------------- idle ----

def brute_idle(ops_by_device, roots, records, delta, step=1e-5):
    """(named, unspanned, total) on a grid, averaged over devices."""
    named, unspanned, total = {}, 0.0, 0.0
    ndev = len(ops_by_device)
    for ops in ops_by_device.values():
        for root in roots:
            lo = root.start_ns * 1e-9 + delta
            hi = root.end_ns * 1e-9 + delta
            t = np.arange(lo, hi, step) + step / 2
            busy = np.zeros_like(t, bool)
            for s, e, *_ in ops:
                busy |= (t >= s) & (t < e)
            kids = sred.below(records, [root])
            for k, ti in enumerate(t):
                if busy[k]:
                    continue
                total += step / ndev
                cover = [r for r in kids
                         if r.start_ns * 1e-9 + delta <= ti
                         < r.end_ns * 1e-9 + delta]
                if not cover:
                    unspanned += step / ndev
                    continue
                inner = min(cover, key=lambda r: r.end_ns - r.start_ns)
                named[inner.name] = named.get(inner.name, 0.0) + step / ndev
    return named, unspanned, total


def test_idle_attribution_matches_a_brute_force_grid():
    book, _ = loop_book(warm=0, window=2)
    recs = book.closed()
    roots = sred.window(recs, 2, T)
    delta = 100.0

    def at(ms):
        return ms * 1e-3 + delta

    ops = {"/device:TPU:0": [(at(3), at(8), "fusion", "jit_a"),
                             (at(31), at(34), "fusion", "jit_b"),
                             (at(51), at(70.5), "sort", "jit_c"),
                             (at(104), at(250), "while", "jit_d"),
                             (at(330), at(352), "fusion", "jit_b")],
           "/device:TPU:1": [(at(0), at(20), "fusion", "jit_a")]}
    named, unspanned, total = sred.attribute_idle(ops, roots, recs, delta)
    want = brute_idle(ops, roots, recs, delta)
    assert total == pytest.approx(want[2], abs=2e-4)
    assert unspanned == pytest.approx(want[1], abs=2e-4)
    assert set(named) == set(want[0])
    for k, v in want[0].items():
        assert named[k] == pytest.approx(v, abs=2e-4)
    # readbacks are innermost inside the kNN stage
    assert named[sred.READBACK] > 0 and named["approx.knn"] > 0


def test_the_window_idle_share_names_the_unspanned_part():
    book, _ = loop_book(warm=1, window=2)
    recs = book.closed()
    roots = sred.window(recs, 2, T)
    tr = tred.Trace(window=(0.0, 1e4))
    tr.ops["/device:TPU:0"] = [(DELTA, DELTA + 1e-3, "fusion", "jit_a")]
    tr.host = bench_spans(book)
    got = sred.numbers(recs, roots, tr, T)
    named, unspanned, total = sred.attribute_idle(tr.ops, roots, recs, DELTA)
    assert got["idle_unspanned"] == pytest.approx(100 * unspanned / total,
                                                  abs=1e-3)
    # per window fit: 100 ms, 90 of them under a stage; assess 10 ms
    assert got["idle_unspanned"] == pytest.approx(100 * 2 * 20 / 220,
                                                  abs=0.1)


# ---------------------------------------------------------- readers ----

def span_metrics():
    spec = harness.load_json(REPO / "BENCHMARK.json")
    return [m for m in spec["per_layer"] if m["source"] == "program_span"]


def test_every_span_metric_has_a_reader_and_none_untraced():
    names = [m["name"] for m in span_metrics()]
    assert len(names) == 9
    for name in names:
        run = harness.Run(cell=harness.load_cell(
            "acl-embed-fit63k" if name.endswith(".audit") else
            "spotify-fit50k"), seed=0, seconds=1.0)
        run.attempted = run.completed_in_window = 3
        assert harness.reader(name)(run) is None


def test_cpu_traced_loop_yields_every_span_number():
    spans.clear()
    cell = harness.Cell(name="tiny", config={
        "fit": {"method": "approx", "metric": "cosine", "knn_k": 5},
        "expect_method": "approx"}, mix={"loop": "library"}, chips=1,
        trace_seconds=0.0, limits={})
    run = harness.Run(cell=cell, seed=0, seconds=0.0,
                      notes={"t_start": 0.0})
    rng = np.random.default_rng(3)
    X = rng.normal(size=(300, 16)).astype(np.float32)
    answers, run.trace = harness.traced(
        lambda annotate: harness.library_loop(run, X, 0.0, annotate,
                                              harness.CompileCounter()))
    assert run.attempted == len(answers) == 1
    lo, hi = sred.clock_offset(run.trace.host, spans.recent(),
                               threading.get_ident())
    assert 0 <= hi - lo < 1e-3
    got = {}
    for m in span_metrics():
        v = harness.reader(m["name"])(run)
        if v is not None:
            got[m["name"]] = v
    # the CPU has no device ops, so only the idle shares are missing
    assert set(got) == {m["name"] for m in span_metrics()} - {
        "idle_unspanned.fit", "idle_unspanned.audit"}
    assert got["readbacks.audit"] >= 1 + 1 + 2 + 4 + 2
    assert all(v >= 0 for v in got.values())
    assert got["prepass_ms.audit"] == got["prepass_ms.fit"] > 0
