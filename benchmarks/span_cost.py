"""Host cost of one ``repro.spans`` span, without and with a profiler.

  PYTHONPATH=src python benchmarks/span_cost.py [--spans 100000]

Times N empty ``with span(...)`` blocks, less the same loop without the
span, best of five, and prints microseconds per span: first with no
profiler running (the cost every caller pays), then under
``jax.profiler.trace`` (the cost of a traced run).  The ring is cleared
before each loop so eviction is not counted twice.
"""
from __future__ import annotations

import argparse
import tempfile
import time


def per_span_us(n: int) -> float:
    from repro import spans
    best = float("inf")
    for _ in range(5):
        spans.clear()
        t0 = time.perf_counter()
        for _ in range(n):
            with spans.span("cost"):
                pass
        t1 = time.perf_counter()
        for _ in range(n):
            pass
        t2 = time.perf_counter()
        best = min(best, (t1 - t0) - (t2 - t1))
    spans.clear()
    return best / n * 1e6


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--spans", type=int, default=100_000)
    args = ap.parse_args(argv)
    import jax
    off = per_span_us(args.spans)
    with tempfile.TemporaryDirectory() as d, jax.profiler.trace(d):
        on = per_span_us(args.spans)
    print(f"{jax.devices()[0].platform}: {off:.3f} us per span without a "
          f"profiler, {on:.3f} us under jax.profiler.trace")


if __name__ == "__main__":
    main()
