"""Control run for the precision of the Gram dots, on a TPU.

Runs the oracle phase of ``chip_smoke.py`` (certify_fit against the f64
naive-Prim oracle at the smoke's sizes and widths) with
``repro.kernels.ref.GRAM_PRECISION`` lowered to ``Precision.DEFAULT``,
the precision every Gram dot had before the dots named one.  On a TPU
an f32 dot at DEFAULT precision is one bf16 pass; the cells it prints
show what that costs against the oracle, and why the dots run at
HIGHEST.  The exit code is 0 when the control ran, whatever the cells
say, and 2 when the first device is not a TPU.

  python -m benchmarks.precision_control     # from the repository root
"""
from __future__ import annotations

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main() -> int:
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"precision_control: no TPU (first device is {dev.platform})",
              file=sys.stderr)
        return 2
    import chip_smoke
    from repro.kernels import ref
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    ref.GRAM_PRECISION = jax.lax.Precision.DEFAULT
    run = chip_smoke.Run()
    chip_smoke.oracle_phase(run, chip_smoke.FULL)
    print(f"precision control (Gram dots at DEFAULT) on {dev.device_kind}: "
          f"{'cells off the oracle' if run.failed else 'every cell ok'}",
          flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
