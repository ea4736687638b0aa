"""From a profiler trace to the numbers the per-layer metrics read.

``collect`` turns a ``jax.profiler.ProfileData`` into plain tuples; the
rest are pure functions of those tuples, so the arithmetic is tested on a
small recorded trace without a chip.

  device op     an event on the "XLA Ops" line of a device plane
                ("/device:TPU:<i>"); busy time is the union of their
                intervals inside the traced window.
  program       an event on the "XLA Modules" line: one execution of one
                compiled program, named after its jitted function
                ("jit_vat_matrix_free", ...).
  host span     an event named "bench.*" on any host thread: the
                benchmark's own ``TraceAnnotation`` around each call.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field

WINDOW_SPAN = "bench.window"
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")


@dataclass
class Trace:
    """Plain-tuple view of one trace; times in seconds.

    Attributes:
      ops: device name -> [(start, end, op name, program name)].
      programs: device name -> [(start, end, program name)].
      host: [(start, end, span name)] of the benchmark's spans.
      window: (start, end) of the traced window, or None.
    """
    ops: dict = field(default_factory=dict)
    programs: dict = field(default_factory=dict)
    host: list = field(default_factory=list)
    window: tuple | None = None


def program_name(raw: str) -> str:
    """Strip the id XLA appends ("jit_f(123)" -> "jit_f")."""
    return raw.split("(", 1)[0].strip()


def op_name(raw: str) -> str:
    """The HLO instruction's name of a device op event, whose name may be
    the whole instruction ("%fusion.68 = f32[1024] fusion(...)")."""
    return raw.split(" = ", 1)[0].strip().lstrip("%")


def _stat(event, key):
    try:
        for k, v in event.stats:
            if k == key:
                return v
    except (AttributeError, TypeError, ValueError):
        return None
    return None


def collect(profile) -> Trace:
    """Read device ops, programs and benchmark spans from ProfileData."""
    tr = Trace()
    for plane in profile.planes:
        if _DEVICE_PLANE.match(plane.name):
            ops, progs = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for ev in line.events:
                        s = ev.start_ns * 1e-9
                        mod = _stat(ev, "hlo_module") or ""
                        ops.append((s, s + ev.duration_ns * 1e-9,
                                    op_name(ev.name), program_name(str(mod))))
                elif line.name == "XLA Modules":
                    for ev in line.events:
                        s = ev.start_ns * 1e-9
                        progs.append((s, s + ev.duration_ns * 1e-9,
                                      program_name(ev.name)))
            tr.ops[plane.name] = ops
            tr.programs[plane.name] = progs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        s = ev.start_ns * 1e-9
                        tr.host.append((s, s + ev.duration_ns * 1e-9,
                                        ev.name))
    spans = [(s, e) for s, e, n in tr.host if n == WINDOW_SPAN]
    if spans:
        tr.window = (min(s for s, _ in spans), max(e for _, e in spans))
    return tr


def merge(intervals, lo: float, hi: float) -> list:
    """Union of (start, end, ...) intervals clipped to [lo, hi]."""
    out: list = []
    for s, e, *_ in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_seconds(intervals, lo: float, hi: float) -> float:
    return sum(e - s for s, e in merge(intervals, lo, hi))


def device_busy(tr: Trace):
    """(mean busy seconds over the devices, window seconds), or None when
    the trace holds no window or no device op."""
    if tr.window is None or not any(tr.ops.values()):
        return None
    lo, hi = tr.window
    busy = [busy_seconds(ops, lo, hi) for ops in tr.ops.values()]
    return sum(busy) / len(busy), hi - lo


def program_seconds(tr: Trace, names) -> float | None:
    """Device seconds of the named programs inside the window, averaged
    over the devices that ran any of them; None when none ran."""
    if tr.window is None:
        return None
    lo, hi = tr.window
    wanted = set(names)
    per = []
    for progs in tr.programs.values():
        mine = [p for p in progs if p[2] in wanted]
        if mine:
            per.append(busy_seconds(mine, lo, hi))
    return sum(per) / len(per) if per else None


def idle_gaps(tr: Trace, top: int = 10) -> list:
    """The longest idle gaps of the first device in the window, each named
    by the innermost benchmark span that covers its middle."""
    if tr.window is None or not tr.ops:
        return []
    lo, hi = tr.window
    first = sorted(tr.ops)[0]
    busy = merge(tr.ops[first], lo, hi)
    edges = [lo] + [x for s, e in busy for x in (s, e)] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[1] - g[0], reverse=True)
    named = []
    spans = [h for h in tr.host if h[2] != WINDOW_SPAN]
    for s, e in gaps[:top]:
        mid = (s + e) / 2
        cover = [h for h in spans if h[0] <= mid <= h[1]]
        name = min(cover, key=lambda h: h[1] - h[0])[2] if cover else "none"
        named.append([name, e - s])
    return named


def top_ops(tr: Trace, top: int = 10) -> list:
    """Device ops that took most time in the window, summed by
    "program/op" name and averaged over the devices."""
    if tr.window is None or not tr.ops:
        return []
    lo, hi = tr.window
    total: dict = {}
    for ops in tr.ops.values():
        for s, e, name, prog in ops:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                key = f"{prog}/{name}" if prog else name
                total[key] = total.get(key, 0.0) + d
    ndev = len(tr.ops)
    ranked = sorted(total.items(), key=lambda kv: kv[1], reverse=True)
    return [[k, v / ndev] for k, v in ranked[:top]]


def top_programs(tr: Trace, top: int = 12) -> list:
    """Programs with the most device time in the window, and their count
    of executions, summed over the devices."""
    if tr.window is None:
        return []
    lo, hi = tr.window
    total: dict = {}
    for progs in tr.programs.values():
        for s, e, name in progs:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                t, c = total.get(name, (0.0, 0))
                total[name] = (t + d, c + 1)
    ranked = sorted(total.items(), key=lambda kv: kv[1][0], reverse=True)
    return [[k, t, c] for k, (t, c) in ranked[:top]]
