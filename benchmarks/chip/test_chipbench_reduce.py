"""The chip benchmark's reduction from trace to metrics, and its refusal
to run without a chip.  No test here loads the TPU library."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
REPO = HERE.parents[1]
sys.path[:0] = [str(HERE), str(REPO / "src")]

import harness  # noqa: E402
import tracereduce as tred  # noqa: E402


def synthetic() -> tred.Trace:
    """Two chips, a 10 s window, overlapping ops, named programs."""
    tr = tred.Trace(window=(100.0, 110.0))
    tr.ops["/device:TPU:0"] = [
        (99.0, 101.0, "fusion.1", "jit_a"),        # clipped to 1 s
        (100.5, 102.0, "fusion.2", "jit_a"),       # overlaps: union 2 s
        (104.0, 105.0, "dot.3", "jit_b"),
        (109.5, 111.0, "fusion.1", "jit_a")]       # clipped to 0.5 s
    tr.ops["/device:TPU:1"] = [(100.0, 101.0, "fusion.1", "jit_a")]
    tr.programs["/device:TPU:0"] = [(99.0, 102.0, "jit_a"),
                                    (104.0, 105.0, "jit_b"),
                                    (109.5, 111.0, "jit_a")]
    tr.programs["/device:TPU:1"] = [(100.0, 101.0, "jit_a")]
    tr.host = [(100.0, 110.0, "bench.window"), (100.0, 103.0, "bench.fit"),
               (103.0, 109.9, "bench.assess"), (105.5, 106.0, "bench.fit")]
    return tr


def brute_busy(intervals, lo, hi, step=1e-3):
    t = np.arange(lo, hi, step) + step / 2
    on = np.zeros_like(t, bool)
    for s, e, *_ in intervals:
        on |= (t >= s) & (t < e)
    return on.sum() * step


def test_busy_is_the_union_of_op_intervals_in_the_window():
    tr = synthetic()
    ops0 = tr.ops["/device:TPU:0"]
    assert tred.busy_seconds(ops0, 100.0, 110.0) == pytest.approx(3.5)
    assert tred.busy_seconds(ops0, 100.0, 110.0) == pytest.approx(
        brute_busy(ops0, 100.0, 110.0), abs=1e-2)
    busy, window = tred.device_busy(tr)
    assert window == pytest.approx(10.0)
    assert busy == pytest.approx((3.5 + 1.0) / 2)      # mean over chips


def test_program_time_is_found_by_name_and_averaged_over_chips():
    tr = synthetic()
    # chip 0: jit_a 2.0 + 0.5 s in the window, chip 1: 1.0 s
    assert tred.program_seconds(tr, ["jit_a"]) == pytest.approx(1.75)
    assert tred.program_seconds(tr, ["jit_b"]) == pytest.approx(1.0)
    assert tred.program_seconds(tr, ["jit_missing"]) is None
    assert tred.program_name("jit_vat_matrix_free(123)") == \
        "jit_vat_matrix_free"


def test_idle_gaps_are_named_by_the_innermost_span():
    gaps = tred.idle_gaps(synthetic())
    # chip 0 idles 105-109.5, whose middle (107.25) lies in assess only,
    # and 102-104, whose middle (103.0) both fit and assess cover: the
    # shorter, innermost span names it
    assert [g[0] for g in gaps] == ["bench.assess", "bench.fit"]
    assert gaps[0][1] == pytest.approx(4.5)
    assert sum(g[1] for g in gaps) == pytest.approx(10.0 - 3.5)


def test_top_ops_sum_by_program_and_op():
    top = dict(tred.top_ops(synthetic()))
    assert top["jit_a/fusion.1"] == pytest.approx((1.0 + 0.5 + 1.0) / 2)
    assert top["jit_b/dot.3"] == pytest.approx(0.5)


def recorded_trace() -> tred.Trace:
    """An excerpt of a TPU v5e trace of the spotify-fit50k cell."""
    raw = json.loads((HERE / "testdata" / "fit50k_trace_excerpt.json")
                     .read_text())
    tr = tred.Trace(window=tuple(raw["window"]))
    tr.ops = {k: [tuple(x) for x in v] for k, v in raw["ops"].items()}
    tr.programs = {k: [tuple(x) for x in v]
                   for k, v in raw["programs"].items()}
    tr.host = [tuple(x) for x in raw["host"]]
    return tr


def test_recorded_chip_trace_reduces_to_its_brute_force_numbers():
    tr = recorded_trace()
    lo, hi = tr.window
    busy, window = tred.device_busy(tr)
    ops = next(iter(tr.ops.values()))
    assert busy == pytest.approx(brute_busy(ops, lo, hi, step=(hi - lo)
                                            / 200_000), rel=1e-3)
    assert 0.0 < busy <= window
    names = {p[2] for progs in tr.programs.values() for p in progs}
    assert "jit_vat_matrix_free" in names


def test_roofline_share_is_the_least_time_over_program_time():
    run = harness.Run(cell=harness.load_cell("spotify-fit50k"), seed=0,
                      seconds=4.0)
    run.completed_in_window = 4
    run.peaks = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
    tr = tred.Trace(window=(0.0, 4.0))
    tr.programs["/device:TPU:0"] = [(0.1 + i, 0.9 + i, "jit_vat_matrix_free")
                                    for i in range(4)]
    run.trace = tr
    share = harness.reader("traversal_roofline.fit")(run)
    n, d = 50_000, 13
    least = n * (n - 1) / 2 * 2 * d / 197e12      # compute binds
    assert least > n * d * 4 / 819e9
    assert share == pytest.approx(100.0 * least * 4 / 3.2)
    run.trace = tred.Trace(window=(0.0, 4.0))     # nothing to read
    assert harness.reader("traversal_roofline.fit")(run) is None
    assert harness.reader("device_idle.fit")(run) is None


def test_cpu_trace_yields_spans_and_no_device_numbers():
    import jax
    import jax.numpy as jnp

    def work(annotate):
        with annotate(tred.WINDOW_SPAN):
            with annotate("bench.fit"):
                jax.block_until_ready(jnp.arange(1000.0).sum())

    _, tr = harness.traced(work)
    names = {h[2] for h in tr.host}
    assert {tred.WINDOW_SPAN, "bench.fit"} <= names
    assert tr.window is not None and tr.window[1] > tr.window[0]
    assert tred.device_busy(tr) is None          # the CPU is no device


def _run_cli(cwd: Path, env_extra=None):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "PYTHONPATH")}
    env["JAX_PLATFORMS"] = "cpu"
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "spotify-fit50k", "--seed", "4294967311", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def _no_result(out: str) -> bool:
    lines = [ln for ln in out.splitlines() if ln.strip()]
    return not lines or not lines[-1].lstrip().startswith("{")


def test_run_exits_nonzero_without_a_tpu():
    p = _run_cli(REPO)
    assert p.returncode != 0
    assert _no_result(p.stdout)
    assert "no TPU" in p.stderr


def test_run_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_cli(tmp_path)
    assert p.returncode != 0
    assert _no_result(p.stdout)
