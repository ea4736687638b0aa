"""Host spans: the program's own record of where its host time goes.

``span(name)`` marks one stage of host work — an admission check, the
numerics pre-pass, a stage of the approx rung, a wait for a device
result.  Each span enters ``jax.profiler.TraceAnnotation(name)``, so
under a running profiler it lands in the same trace, on the same clock,
as the device ops; and on exit (an exception included) it appends one
closed ``Span`` record to an in-memory ring of ``RING`` records, which
``recent()`` reads.

The recorder is always on and has no switch, like the serving layer's
counters: a span costs a couple of microseconds of host time and adds no
synchronisation with the device — a span around an asynchronous
dispatch measures the enqueue, not the device work.  Spans are opened
on the host only, never inside a jitted or traced function.

Each record carries its parent (the innermost span open on the same
thread when it began) and its root (the outermost one), so every span
of one assessment shares the root of its ``fastvat.fit`` or
``fastvat.assess``.  ``readback`` is the one way the approx rung turns
a device array into a host array, so its ``device.readback`` spans both
time and count the host's blocking waits.

>>> from repro import spans
>>> with spans.span("outer"):
...     with spans.span("inner"):
...         pass
>>> inner, outer = spans.recent()[-2:]
>>> (inner.name, inner.parent == outer.id, inner.root == outer.id)
('inner', True, True)
"""
from __future__ import annotations

import collections
import functools
import itertools
import threading
import time
from typing import NamedTuple

import numpy as np

import jax

#: Records the ring keeps; older ones are evicted and counted by
#: ``dropped()``.
RING = 1 << 16


class Span(NamedTuple):
    """One closed span; times from ``time.perf_counter_ns()``.

    Attributes:
      name: the stage, e.g. "approx.knn".
      id: this span's id, unique in the process.
      parent: id of the span open around it on its thread, or None.
      root: id of the outermost span open on its thread (its own id
        when it is a root).
      thread: ``threading.get_ident()`` of the thread that ran it.
      start_ns, end_ns: entry and exit.
      seq: position in the order spans closed, increasing.
    """
    name: str
    id: int
    parent: int | None
    root: int
    thread: int
    start_ns: int
    end_ns: int
    seq: int


_ring: collections.deque = collections.deque(maxlen=RING)
_lock = threading.Lock()
_ids = itertools.count(1)
_seq = 0
_dropped = 0
_open = threading.local()
_now = time.perf_counter_ns
_annotation = jax.profiler.TraceAnnotation


class span:
    """Context manager (or decorator) recording one host span.

    ``with span("approx.walk"): ...`` records the block;
    ``@span("api.admission")`` records every call of a function.
    """

    __slots__ = ("name", "_id", "_parent", "_root", "_start", "_ann")

    def __init__(self, name: str):
        self.name = name

    def __call__(self, fn):
        name = self.name

        @functools.wraps(fn)
        def spanned(*args, **kwargs):
            with span(name):
                return fn(*args, **kwargs)
        return spanned

    def __enter__(self) -> "span":
        try:
            stack = _open.stack
        except AttributeError:
            stack = _open.stack = []
        self._id = sid = next(_ids)
        if stack:
            self._parent, self._root = stack[-1], stack[0]
        else:
            self._parent, self._root = None, sid
        stack.append(sid)
        self._ann = _annotation(self.name)
        self._ann.__enter__()
        self._start = _now()
        return self

    def __exit__(self, *exc) -> bool:
        global _seq, _dropped
        end = _now()
        self._ann.__exit__(*exc)
        _open.stack.remove(self._id)
        # tuple.__new__ builds the record at a third of Span(...)'s cost
        fields = (self.name, self._id, self._parent, self._root,
                  threading.get_ident(), self._start, end)
        with _lock:
            if len(_ring) == RING:
                _dropped += 1
            _ring.append(tuple.__new__(Span, fields + (_seq,)))
            _seq += 1
        return False


def recent() -> tuple[Span, ...]:
    """The ring's records, oldest first (in the order they closed)."""
    with _lock:
        return tuple(_ring)


def dropped() -> int:
    """How many records the ring has evicted since the last ``clear``."""
    return _dropped


def clear() -> None:
    """Empty the ring and reset ``dropped()`` (for tests)."""
    global _dropped
    with _lock:
        _ring.clear()
        _dropped = 0


def readback(x, dtype=None) -> np.ndarray:
    """``np.asarray(x, dtype)``; a device array is read under a
    ``device.readback`` span, which covers the host's wait for the
    result and its copy to the host."""
    if not isinstance(x, jax.Array):
        return np.asarray(x, dtype)
    with span("device.readback"):
        return np.asarray(x, dtype)
