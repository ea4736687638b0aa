"""``correct`` holds for the program as it is and fails for the control
and for each fault a cell can have.

Each test drives a whole run (set-up, window, reference comparison)
through ``harness.execute`` on the cells cut to CPU sizes
(``smallcells.py``), skipping only the look for a chip.  The faults are
planted in the program underneath, where the answer is produced.
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
REPO = HERE.parents[1]
sys.path[:0] = [str(HERE), str(REPO / "src")]

import control  # noqa: E402
import harness  # noqa: E402
import smallcells  # noqa: E402

ONE_CHIP = ["spotify-fit50k", "acl-embed-fit63k"]
SPEC = json.loads((REPO / "BENCHMARK.json").read_text())


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return smallcells.make(tmp_path_factory.mktemp("cells") / "chip")


def run_cell(bench, cell, seed=20260, seconds=0.5):
    return harness.execute(cell, seed, seconds, False, spec=SPEC,
                           t_start=time.perf_counter(), bench_dir=bench,
                           chip_check=False, log=lambda msg: None)


def failing(result):
    return {k for k, c in result["checks"].items()
            if not c["value"] <= c["limit"]}


@pytest.mark.parametrize("cell", ONE_CHIP)
def test_sound_run_is_correct(bench, cell):
    r = run_cell(bench, cell)
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert set(r["metrics"]) >= {"setup_s"}


@pytest.mark.parametrize("cell", ONE_CHIP)
def test_control_one_precision_lower_is_not_correct(bench, cell):
    with control.lowered("high"):
        r = run_cell(bench, cell)
    assert not r["correct"]
    assert failing(r) & {"image_err", "order_gap"}


def swap_two(order):
    o = np.array(order)
    o[[1, len(o) // 2]] = o[[len(o) // 2, 1]]
    return o


# An exact order must be a Prim traversal, so one swap shows; the approx
# rung's order is held only to its tree weight and to the exact MST
# weight, so the fault there is a reversed tenth of the traversal.
@pytest.mark.parametrize("cell,alter", [
    ("spotify-fit50k", swap_two),
    ("acl-embed-fit63k", control.reverse_tenth)])
def test_altered_order_is_caught(bench, cell, alter, monkeypatch):
    from repro.api import registry
    render = registry._band_render

    def altered(Xj, order, meta, opts):
        return render(Xj, alter(order), meta, opts)

    monkeypatch.setattr(registry, "_band_render", altered)
    r = run_cell(bench, cell)
    assert not r["correct"]
    assert failing(r) & {"order_gap", "order_bound", "order_excess"}


def test_control_script_fault_is_caught_and_removed(bench):
    """``control.py --faults reverse-tenth`` plants the fault for its runs
    only: they read not correct, and the program is whole again after."""
    from repro.api import registry
    render = registry._band_render
    with control.FAULTS["reverse-tenth"]():
        r = run_cell(bench, "acl-embed-fit63k")
    assert registry._band_render is render
    assert not r["correct"]
    assert "order_excess" in failing(r)
