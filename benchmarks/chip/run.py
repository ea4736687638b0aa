"""Run one cell of the chip benchmark once.

  python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
      --seconds <s> --trace <0|1>

from the root of a checkout.  One process, one run: it generates the
cell's data from the seed, loads every program the cell's traffic uses
(JAX's persistent compilation cache lives in ``.jax_cache/`` at the root
of the checkout), measures for ``--seconds`` seconds, checks the window's
answers against the plain reference (``reference.py``) and prints one JSON
line last on stdout:

  {"correct", "attempted", "failed", "metrics", "device",
   ["breakdown",] "checks"}

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a profiler trace of a shorter window.  Each number
compared with the reference is printed beside its limit, last on stderr
and under "checks" in the result line.  Without a TPU, or with another
number of chips than the cell asks for, it exits 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402 — the set-up clock starts before imports
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
REPO = HERE.parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # the compile cache sits at a fixed path inside the checkout
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(REPO / ".jax_cache")
    sys.path[:0] = [str(HERE), str(REPO / "src")]
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    phases = {"jax_import": round(time.perf_counter() - T_START, 3)}
    import harness
    try:
        import repro  # noqa: F401 — the system under test must be here
        phases["repro_import"] = round(time.perf_counter() - T_START, 3)
        spec = harness.load_json(REPO / "BENCHMARK.json")
        result = harness.execute(args.workload, args.seed, args.seconds,
                                 bool(args.trace), spec=spec,
                                 t_start=T_START, phases=phases)
    except (harness.BenchError, ImportError, OSError) as exc:
        print(f"chip benchmark: {exc}", file=sys.stderr, flush=True)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
