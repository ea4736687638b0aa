"""The control of ``correct``: the program one precision step lower.

The program's Gram dots (``repro.kernels.ref._dot``) run float32 at
``Precision.HIGHEST``.  The control replaces that one function by an exact
emulation of the next step down and reruns the cell's timed path, so every
number the reference compares sees what a lower-precision program would
answer:

  high      bf16x3: each operand split into a bfloat16 head and a bfloat16
            tail, the three larger partial products summed in float32
            (what ``Precision.HIGH`` computes on a TPU)
  default   one bfloat16 pass (``Precision.DEFAULT`` on a TPU)

The emulation rounds the operands itself and multiplies the rounded parts
at HIGHEST, which is exact for bfloat16 values, so it reads the same on a
TPU and on a CPU.

  python3 benchmarks/chip/control.py --workload spotify-fit50k \\
      --seconds 3 --seeds 11 12 13 ... --control-seeds 21 22 23

Runs, in one process, each seed through the program as it is (the lower
readings of the compared numbers) and each control seed at both lower
precisions (the upper readings), and prints one JSON line per run.  With
``--faults reverse-tenth`` it also runs each control seed with that fault
planted, the upper reading of the numbers that precision does not move.
Not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
REPO = HERE.parents[1]
LEVELS = ("high", "default")


def _bf16(x):
    """Round float32 values to the nearest bfloat16 (ties to even)."""
    import jax.numpy as jnp
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def emulated_dot(level: str):
    """A stand-in for ``repro.kernels.ref._dot`` at a lower precision."""
    import jax.numpy as jnp
    from jax import lax

    def mm(a, b):
        return jnp.matmul(a, b, precision=lax.Precision.HIGHEST)

    def dot(a, b):
        a = a.astype(jnp.float32)
        b = b.astype(jnp.float32)
        ah, bh = _bf16(a), _bf16(b)
        if level == "default":
            return mm(ah, bh)
        al, bl = _bf16(a - ah), _bf16(b - bh)
        return mm(ah, bl) + mm(al, bh) + mm(ah, bh)

    return dot


@contextlib.contextmanager
def lowered(level: str):
    """Run the program with its Gram dots one precision step lower."""
    import jax
    from repro.kernels import ref
    if level not in LEVELS:
        raise ValueError(f"level must be one of {LEVELS}, got {level!r}")
    saved = ref._dot
    jax.clear_caches()
    ref._dot = emulated_dot(level)
    try:
        yield
    finally:
        ref._dot = saved
        jax.clear_caches()


def reverse_tenth(order):
    """The order with the tenth of it after the first point reversed."""
    import numpy as np
    o = np.array(order)
    k = len(o) // 10
    o[1:1 + k] = o[1:1 + k][::-1]
    return o


@contextlib.contextmanager
def reversed_tenth():
    """Run the program with its order altered where it is produced: a
    reversed tenth of the traversal goes into the band render and out as
    the fit's order."""
    from repro.api import registry
    render = registry._band_render

    def altered(Xj, order, meta, opts):
        return render(Xj, reverse_tenth(order), meta, opts)

    registry._band_render = altered
    try:
        yield
    finally:
        registry._band_render = render


FAULTS = {"reverse-tenth": reversed_tenth}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--levels", nargs="*", default=list(LEVELS))
    ap.add_argument("--faults", nargs="*", default=[], choices=sorted(FAULTS),
                    help="faults to plant, each run on the control seeds")
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(REPO / ".jax_cache")
    sys.path[:0] = [str(HERE), str(REPO / "src")]
    import harness
    spec = harness.load_json(REPO / "BENCHMARK.json")

    def one(seed, level):
        t = time.perf_counter()
        r = harness.execute(args.workload, seed, args.seconds, False,
                            spec=spec, t_start=t)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "level": level, "correct": r["correct"],
                          "checks": r["checks"],
                          "seconds": time.perf_counter() - t}), flush=True)

    for seed in args.seeds:
        one(seed, "highest")
    for level in args.levels:
        with lowered(level):
            for seed in args.control_seeds:
                one(seed, level)
    for fault in args.faults:
        with FAULTS[fault]():
            for seed in args.control_seeds:
                one(seed, fault)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
