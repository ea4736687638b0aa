"""LRU-bounded AOT program cache (ISSUE 7 tentpole, part 1).

Every distinct compiled fit path the server can dispatch is named by a
:class:`ProgramKey` — the full set of knobs that change generated code.
The cache maps keys to ``jax.jit(...).lower(...).compile()`` artifacts
so no request ever pays trace/compile time twice: a warm-cache request
runs the stored ``Compiled`` executable without re-entering Python
tracing at all (tests/test_serve.py pins this with a trace census).

The key contract (documented in docs/serving.md and pinned by the
key-distinctness tests): if a knob can alter the jaxpr or the lowered
HLO, it MUST appear in the key.  That is rung, padded shape
(b_bucket, n_bucket, d), metric, device-mesh fingerprint, turbo mode,
kNN fan-out, the Pallas toggle, svat's sample size, and the numerics
shield's resolved plan (tile form + storage dtype).  Seeds and request
deadlines are runtime data, not key material.

Capacity is a hard bound: inserting past it evicts the least recently
used program (compiled artifacts hold device buffers; an unbounded
cache is a memory leak with extra steps).  Hit/miss/eviction counters
are exposed via :meth:`ProgramCache.stats` and surface in the server's
``stats()`` and the bench "serve" table.
"""
from __future__ import annotations

import dataclasses
import threading
from collections import OrderedDict
from typing import Any, Callable

import jax


@dataclasses.dataclass(frozen=True)
class ProgramKey:
    """Identity of one compiled fit program.

    Attributes:
      rung: registry rung name ("vat", "ivat", "flashvat", ...).
      b_bucket: padded lane count (0 while the request is queued and
        the group size is still unknown; see :meth:`with_batch`).
      n_bucket: padded row count (exact n for rungs that cannot be
        row-padded, e.g. flashvat's band renderer).
      d: feature dimension (never padded — it changes the math).
      metric: dissimilarity metric baked into the kernel.
      mesh: device-mesh fingerprint from :func:`mesh_fingerprint`.
      turbo: flashvat engine pin (RungOptions.turbo) — changes the
        generated traversal code.
      knn_k: approx-rung kNN fan-out.
      use_pallas: kernel-dispatch toggle.
      sample_size: svat's maximin sample size.
      num_form: the numerics shield's tile form ("gram" | "direct") —
        resolved host-side per request (``numerics.resolve``) and baked
        statically into the kernels, so it is key material: a
        direct-form batch must never ride a Gram-form program.
      num_dtype: resolved coordinate-storage precision ("f32" | "bf16")
        — bf16 requests that pass certification key separately so their
        quantized lanes never coalesce with full-precision ones.
    """
    rung: str
    b_bucket: int
    n_bucket: int
    d: int
    metric: str
    mesh: str
    turbo: bool | None = None
    knn_k: int = 15
    use_pallas: bool = False
    sample_size: int = 256
    num_form: str = "gram"
    num_dtype: str = "f32"

    def with_batch(self, b_bucket: int) -> "ProgramKey":
        """The same program family at a concrete lane count."""
        return dataclasses.replace(self, b_bucket=b_bucket)


def mesh_fingerprint() -> str:
    """Stable string naming the visible device mesh, e.g. ``"cpu:1"``.

    Programs are compiled against a concrete device set; a different
    mesh is different code, so this lands in every ProgramKey.
    """
    devices = jax.devices()
    return f"{devices[0].platform}:{len(devices)}"


@dataclasses.dataclass(frozen=True)
class CacheStats:
    """Point-in-time counters for a :class:`ProgramCache`."""
    hits: int
    misses: int
    evictions: int
    size: int
    capacity: int

    @property
    def hit_rate(self) -> float:
        total = self.hits + self.misses
        return self.hits / total if total else 0.0


class ProgramCache:
    """Thread-safe LRU map from :class:`ProgramKey` to compiled program.

    ``get`` is the only mutation path: on a miss it calls ``build()``
    (outside nothing — compilation is serialized under the lock, which
    is deliberate: two threads racing to compile the same program would
    both pay the compile and one result would be discarded).
    """

    def __init__(self, capacity: int = 32):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self._capacity = capacity
        self._lock = threading.Lock()
        self._programs: OrderedDict[ProgramKey, Any] = OrderedDict()
        self._hits = 0
        self._misses = 0
        self._evictions = 0

    def get(self, key: ProgramKey, build: Callable[[], Any]) -> Any:
        """Return the program for ``key``, building+caching on miss."""
        with self._lock:
            if key in self._programs:
                self._hits += 1
                self._programs.move_to_end(key)
                return self._programs[key]
            self._misses += 1
            program = build()
            self._programs[key] = program
            while len(self._programs) > self._capacity:
                self._programs.popitem(last=False)
                self._evictions += 1
            return program

    def stats(self) -> CacheStats:
        with self._lock:
            return CacheStats(hits=self._hits, misses=self._misses,
                              evictions=self._evictions,
                              size=len(self._programs),
                              capacity=self._capacity)

    def programs(self) -> dict:
        """Snapshot of the cached ``{ProgramKey: compiled program}``."""
        with self._lock:
            return dict(self._programs)

    def clear(self) -> None:
        with self._lock:
            self._programs.clear()

    def __len__(self) -> int:
        with self._lock:
            return len(self._programs)

    def __contains__(self, key: ProgramKey) -> bool:
        with self._lock:
            return key in self._programs
