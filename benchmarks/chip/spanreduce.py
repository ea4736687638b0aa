"""From the program's own span records to the per-layer metrics that
read them.

The program keeps its host spans in memory (``repro.spans.recent()``):
each record has a name, its own id, its parent's and its root's, the
thread, and start and end on the host's ``perf_counter_ns`` clock.  The
roots of one assessment are ``fastvat.fit`` and ``fastvat.assess``.
Everything here but ``summary`` is a pure function of those records and
of a ``tracereduce.Trace``, so the arithmetic is tested without a chip.

  window        the window's assessments: the last ``run.attempted``
                ``fastvat.fit`` roots on the loop's thread, each with the
                ``fastvat.assess`` root that follows it.
  self time     a span's duration less the part of it that the
                ``device.readback`` spans below it cover: host work,
                without the waits for the device.
  clock         the trace's clock is the recorder's plus an offset δ.
                The benchmark's ``bench.fit`` span encloses its call of
                ``fit``, so the K-th ``bench.fit`` in the trace and the
                K-th ``fastvat.fit`` root bound δ from both sides
                (likewise ``bench.assess``); the middle of the bracket
                maps one clock onto the other.
  idle          the device's idle intervals inside the window's roots,
                each instant named by the innermost non-root program
                span that covers it, or by none.
"""
from __future__ import annotations

import sys
import threading

import tracereduce

FIT, ASSESS = "fastvat.fit", "fastvat.assess"
READBACK = "device.readback"
PREPASS = ("api.admission", "numerics.prepass")


def _roots(records, thread, name):
    return sorted((r for r in records if r.parent is None
                   and r.thread == thread and r.name == name),
                  key=lambda r: r.start_ns)


def window(records, attempted: int, thread: int, dropped: int = 0):
    """The window's roots (fits and the assesses after them), or None
    when fewer than ``attempted`` fit roots were kept, or when the ring
    evicted a record that closed after the first window fit began."""
    mine = sorted((r for r in records if r.parent is None
                   and r.thread == thread and r.name in (FIT, ASSESS)),
                  key=lambda r: r.start_ns)
    fits = [i for i, r in enumerate(mine) if r.name == FIT]
    if attempted <= 0 or len(fits) < attempted:
        return None
    first = mine[fits[-attempted]]
    # the ring evicts in the order records closed: the window is whole
    # when the oldest record kept closed before the window began
    if dropped and min(records, key=lambda r: r.seq).end_ns > first.start_ns:
        return None
    out = []
    for i in fits[-attempted:]:
        out.append(mine[i])
        if i + 1 < len(mine) and mine[i + 1].name == ASSESS:
            out.append(mine[i + 1])
    return out


def below(records, roots) -> list:
    """The non-root spans of the given roots."""
    ids = {r.id for r in roots}
    return [r for r in records if r.root in ids and r.id not in ids]


def union_ns(intervals, lo, hi) -> int:
    return sum(e - s for s, e in tracereduce.merge(intervals, lo, hi))


def self_ns(span, spans) -> int:
    """``span``'s duration less the union of the ``device.readback``
    spans below it (``spans`` holds at least its subtree)."""
    kids: dict = {}
    for r in spans:
        kids.setdefault(r.parent, []).append(r)
    waits, todo = [], [span.id]
    while todo:
        for c in kids.get(todo.pop(), ()):
            todo.append(c.id)
            if c.name == READBACK:
                waits.append((c.start_ns, c.end_ns))
    return (span.end_ns - span.start_ns) - union_ns(waits, span.start_ns,
                                                    span.end_ns)


def covered_ns(spans, names) -> int:
    """Union of the named spans' intervals."""
    got = [(r.start_ns, r.end_ns) for r in spans if r.name in names]
    return union_ns(got, min((s for s, _ in got), default=0),
                    max((e for _, e in got), default=0))


def clock_offset(host, records, thread):
    """(lo, hi) bracket of δ (seconds; trace = recorder + δ) from the
    benchmark's ``bench.fit``/``bench.assess`` spans in the trace and
    the program's roots, paired from the end; None when a pair cannot
    hold (a benchmark span that does not contain its root)."""
    lo, hi, pairs = float("-inf"), float("inf"), 0
    for label, name in (("bench.fit", FIT), ("bench.assess", ASSESS)):
        bench = sorted(h for h in host if h[2] == label)
        roots = _roots(records, thread, name)
        if len(roots) < len(bench):
            return None
        for b, r in zip(bench, roots[len(roots) - len(bench):]):
            lo = max(lo, b[0] - r.start_ns * 1e-9)
            hi = min(hi, b[1] - r.end_ns * 1e-9)
            pairs += 1
    return (lo, hi) if pairs and lo <= hi else None


def _innermost(spans, delta):
    """Sorted, disjoint (start, end, name) segments, in trace seconds,
    each naming the innermost span that covers it."""
    events = []
    for k, r in enumerate(spans):
        s, e = r.start_ns * 1e-9 + delta, r.end_ns * 1e-9 + delta
        events.append((s, 1, -e, k))
        events.append((e, 0, 0.0, k))
    events.sort()
    out, stack, t0 = [], [], None
    for t, opens, _, k in events:
        if stack and t > t0:
            out.append((t0, t, spans[stack[-1]].name))
        t0 = t
        if opens:
            stack.append(k)
        else:
            stack.remove(k)
    return out


def _idle(busy, lo, hi):
    edges = [lo] + [x for s, e in busy for x in (s, e)] + [hi]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def attribute_idle(ops_by_device, roots, records, delta):
    """Device-idle seconds inside ``roots`` by innermost non-root span,
    averaged over the devices: ({name: s}, unspanned s, total s)."""
    named: dict = {}
    unspanned = total = 0.0
    ndev = max(len(ops_by_device), 1)
    for ops in ops_by_device.values():
        ops = sorted(ops)
        for root in roots:
            lo = root.start_ns * 1e-9 + delta
            hi = root.end_ns * 1e-9 + delta
            idle = _idle(tracereduce.merge(ops, lo, hi), lo, hi)
            segs = _innermost(below(records, [root]), delta)
            j = 0
            for s, e in idle:
                total += (e - s) / ndev
                hit = 0.0
                while j < len(segs) and segs[j][1] <= s:
                    j += 1
                k = j
                while k < len(segs) and segs[k][0] < e:
                    d = min(e, segs[k][1]) - max(s, segs[k][0])
                    if d > 0:
                        named[segs[k][2]] = named.get(segs[k][2], 0.0) \
                            + d / ndev
                        hit += d
                    k += 1
                unspanned += (e - s - hit) / ndev
    return named, unspanned, total


def numbers(records, roots, trace=None, thread=None, log=None) -> dict:
    """Every span metric of one window, per assessment (ms, counts, %)."""
    count = sum(r.name == FIT for r in roots)
    spans = below(records, roots)
    per = 1e-6 / count
    out = {
        "prepass_ms": covered_ns(spans, PREPASS) * per,
        "knn_host_ms": sum(self_ns(r, spans) for r in spans
                           if r.name == "approx.knn") * per,
        "boruvka_host_ms": sum(self_ns(r, spans) for r in spans
                               if r.name == "approx.boruvka") * per,
        "walk_ms": covered_ns(spans, ("approx.walk",)) * per,
        "readback_ms": sum(r.end_ns - r.start_ns for r in spans
                           if r.name == READBACK) * per,
        "readbacks": sum(r.name == READBACK for r in spans) / count,
    }
    if trace is None or not trace.ops:
        return out
    bracket = clock_offset(trace.host, records, thread)
    if bracket is None:
        if log:
            log("span clock: no offset fits every bench/program pair")
        return out
    lo, hi = bracket
    named, unspanned, total = attribute_idle(trace.ops, roots, records,
                                             (lo + hi) / 2)
    if log:
        log(f"span clock: offset bracket width {(hi - lo) * 1e3!r} ms")
        table = {k: round(v * 1e3 / count, 3) for k, v in
                 sorted(named.items(), key=lambda kv: -kv[1])}
        log(f"device idle ms per assessment by program span: {table}, "
            f"unspanned {unspanned * 1e3 / count!r}, total "
            f"{total * 1e3 / count!r}")
    if total > 0:
        out["idle_unspanned"] = 100.0 * unspanned / total
    return out


def summary(run):
    """The span numbers of a traced run, computed once per run; None on
    an untraced run or where the program keeps no spans."""
    if "spans" not in run.notes:
        run.notes["spans"] = _summary(run)
    return run.notes["spans"]


def _summary(run):
    if run.trace is None:
        return None
    try:
        from repro import spans
    except ImportError:
        return None
    records = spans.recent()
    thread = threading.get_ident()
    roots = window(records, run.attempted, thread, spans.dropped())
    if not roots:
        return None
    return numbers(records, roots, run.trace, thread,
                   log=lambda m: print(m, file=sys.stderr, flush=True))
