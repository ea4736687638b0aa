"""The exact rung's wide deployment (``acl-embed-exact50k``: an embedding
table on one chip) and the paper's table auto-sharded over four devices,
as ``FastVAT()`` runs it on a v5e-4 host.

Each run goes through ``harness.execute`` on a ``smallcells`` copy that
this file shrinks further itself, skipping only the look for a chip.  No
benchmark cell runs four chips yet; the four-device cell here is made by
this file in that copy (``spotify-fit50k`` with four chips), and runs in a
subprocess that gives the CPU backend four devices, so ``FastVAT()``
auto-shards there.
"""
from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys
import textwrap
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
import tracereduce as tred  # noqa: E402

SPEC = json.loads((REPO / "BENCHMARK.json").read_text())
WIDE, FOUR = "acl-embed-exact50k", "spotify-fit-4dev"
#: the wide cell at a CPU's size, flashvat forced as ``smallcells`` does,
#: and limits read there (CPU, 12 seeds): the sound runs read image_err
#: <= 5.9e-7 and order_gap <= 3.5e-6, ``control.lowered("high")`` >= 2.4e-6
#: and >= 1.75e-5 (6 seeds)
WIDE_SIZE = (3000, 96)
WIDE_LIMITS = {"order_gap": 8e-6, "image_err": 1.2e-6}
#: rows of the four-chip cell: at least FLASH_SHARD_MIN_N, so auto shards
FOUR_ROWS = 4200
#: the tables both sides of the bit-for-bit test assess, by metric
TABLES = {"euclidean": {"rows": FOUR_ROWS, "fields": 13, "standardize": True},
          "cosine": {"rows": FOUR_ROWS, "fields": 96, "standardize": False}}
MIXTURE = {"components": 8, "separation": 3.0}


def shrink(bench: Path, config: str, rows: int, fields: int) -> None:
    p = bench / "configs" / f"{config}.json"
    c = json.loads(p.read_text())
    c["rows"], c["fields"] = rows, fields
    c["fit"]["method"] = "flashvat"
    p.write_text(json.dumps(c))


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    dest = smallcells.make(tmp_path_factory.mktemp("cells") / "chip")
    shrink(dest, "acl-embed-768-exact", *WIDE_SIZE)
    p = dest / "workloads" / f"{WIDE}.json"
    w = json.loads(p.read_text())
    w["limits"] = WIDE_LIMITS
    p.write_text(json.dumps(w))
    shrink(dest, "spotify-audio", FOUR_ROWS, 13)
    w = json.loads((dest / "workloads" / "spotify-fit50k.json").read_text())
    w["chips"] = 4
    (dest / "workloads" / f"{FOUR}.json").write_text(json.dumps(w))
    return dest


def run_cell(bench, cell, seed=20260, seconds=0.01):
    return harness.execute(cell, seed, seconds, False, spec=SPEC,
                           t_start=time.perf_counter(), bench_dir=bench,
                           chip_check=False, log=lambda msg: None)


def failing(result):
    return {k for k, c in result["checks"].items()
            if not c["value"] <= c["limit"]}


def swap_two(order):
    o = np.array(order)
    o[[1, len(o) // 2]] = o[[len(o) // 2, 1]]
    return o


def test_wide_cell_is_correct_and_runs_the_solo_engine(bench):
    from repro import spans
    spans.clear()
    r = run_cell(bench, WIDE)
    assert r["correct"], r["checks"]
    assert set(r["checks"]) >= set(WIDE_LIMITS)
    names = [s.name for s in spans.recent()]
    fits = names.count("fastvat.fit")
    assert fits == r["attempted"] + 2          # two warm assessments
    assert names.count("exact.solo") == fits
    assert names.count("render.band") == fits
    assert "exact.sharded" not in names


def test_wide_cell_one_precision_lower_is_not_correct(bench):
    with control.lowered("high"):
        r = run_cell(bench, WIDE)
    assert not r["correct"]
    assert failing(r) & {"image_err", "order_gap"}


def test_wide_cell_with_a_swapped_pair_is_not_correct(bench, monkeypatch):
    from repro.api import registry
    render = registry._band_render
    monkeypatch.setattr(registry, "_band_render",
                        lambda Xj, order, meta, opts:
                        render(Xj, swap_two(order), meta, opts))
    r = run_cell(bench, WIDE)
    assert not r["correct"]
    assert "order_gap" in failing(r)


# The four-device half: one subprocess runs the cell, the same assessments
# the library's way, and the cell again with the sharded step's
# cross-device pick broken.  It writes what it saw to ``out``.
FOUR_SCRIPT = textwrap.dedent("""
    import json, os, sys, time
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    bench, out = sys.argv[1], sys.argv[2]
    sys.path[:0] = [{here!r}]
    from pathlib import Path
    import numpy as np, jax
    import harness, traffic
    from repro import FastVAT, spans
    from repro.core import distributed
    assert len(jax.devices()) == 4
    spec = json.load(open({spec!r}))

    def cell():
        return harness.execute({four!r}, 20260, 0.01, False, spec=spec,
                               t_start=time.perf_counter(),
                               bench_dir=Path(bench),
                               chip_check=False, log=lambda m: None)

    got = {{}}
    spans.clear()
    r = cell()
    names = [s.name for s in spans.recent()]
    got["sound"] = {{"correct": r["correct"], "checks": r["checks"],
                    "fits": names.count("fastvat.fit"),
                    "sharded": names.count("exact.sharded"),
                    "solo": names.count("exact.solo")}}
    arrays = {{}}
    for metric, table in {tables!r}.items():
        X = traffic.table(dict(table, assumed={mixture!r}), 7)
        spans.clear()
        fv = FastVAT(metric=metric).fit(X)
        rep = fv.assess()
        arrays[metric + "_order"] = np.asarray(fv.result.order)
        arrays[metric + "_rstar"] = np.asarray(fv.result.rstar)
        got[metric] = {{"report": dict(rep),
                       "sharded": [s.name for s in spans.recent()].count(
                           "exact.sharded")}}
    np.savez(out + ".npz", **arrays)

    class LocalPick:
        # every non-tiled all_gather hands each device its own value only,
        # so each continues from its own local winner
        def __getattr__(self, name):
            return getattr(jax.lax, name)

        @staticmethod
        def all_gather(x, axis, *, tiled=False, **kw):
            if tiled:
                return jax.lax.all_gather(x, axis, tiled=True, **kw)
            return x[None]

    distributed.lax = LocalPick()
    distributed._flash_sharded_program.cache_clear()
    jax.clear_caches()
    r = cell()
    got["local_pick"] = {{"correct": r["correct"], "checks": r["checks"]}}
    with open(out + ".json", "w") as f:
        json.dump(got, f)
""")


@pytest.fixture(scope="module")
def four(bench, tmp_path_factory):
    out = str(tmp_path_factory.mktemp("four") / "got")
    script = FOUR_SCRIPT.format(here=str(HERE), four=FOUR, tables=TABLES,
                                mixture=MIXTURE,
                                spec=str(REPO / "BENCHMARK.json"))
    # JAX_PLATFORMS=cpu: without it backend init can hang probing for a TPU
    r = subprocess.run(
        [sys.executable, "-c", script, str(bench), out],
        capture_output=True, text=True, timeout=600,
        env={"PYTHONPATH": str(REPO / "src"), "PATH": os.environ["PATH"],
             "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stderr[-3000:]
    got = json.loads(Path(out + ".json").read_text())
    got["arrays"] = dict(np.load(out + ".npz"))
    return got


def test_four_chip_cell_is_correct_and_shards_every_fit(four):
    s = four["sound"]
    assert s["correct"], s["checks"]
    assert s["fits"] >= 3 and s["sharded"] == s["fits"] and s["solo"] == 0


def test_four_chip_cell_with_a_local_pick_is_not_correct(four):
    r = four["local_pick"]
    assert not r["correct"]
    assert r["checks"]["permutation"]["value"] == 1.0


@pytest.mark.parametrize("metric", sorted(TABLES))
def test_four_devices_equal_one_device_bit_for_bit(four, metric):
    import traffic
    from repro import FastVAT
    X = traffic.table(dict(TABLES[metric], assumed=MIXTURE), 7)
    fv = FastVAT(metric=metric).fit(X)
    rep = json.loads(json.dumps(dict(fv.assess())))    # as the script saw
    assert four[metric]["sharded"] == 1
    np.testing.assert_array_equal(four["arrays"][metric + "_order"],
                                  np.asarray(fv.result.order))
    np.testing.assert_array_equal(four["arrays"][metric + "_rstar"],
                                  np.asarray(fv.result.rstar))
    assert four[metric]["report"] == rep


# ----------------------- the fit readers in the wide cell and on four chips ----

def recorded(name: str) -> tred.Trace:
    """A recorded excerpt of a TPU v5e trace: fit4_* of the sharded
    traversal on four chips (0.2 ms inside one traversal), fit50k_* of the
    one-chip spotify-fit50k cell."""
    raw = json.loads((HERE / "testdata" / name).read_text())
    tr = tred.Trace(window=tuple(raw["window"]))
    tr.ops = {k: [tuple(x) for x in v] for k, v in raw["ops"].items()}
    tr.programs = {k: [tuple(x) for x in v]
                   for k, v in raw["programs"].items()}
    tr.host = [tuple(x) for x in raw["host"]]
    return tr


def brute_busy(intervals, lo, hi, points=200_000):
    step = (hi - lo) / points
    t = lo + (np.arange(points) + 0.5) * step
    on = np.zeros(points, bool)
    for s, e, *_ in intervals:
        on |= (t >= s) & (t < e)
    return on.sum() * step


def traced_run(cell, trace, completed=1, chips=None):
    c = harness.load_cell(cell)
    if chips is not None:
        c = dataclasses.replace(c, chips=chips)
    run = harness.Run(cell=c, seed=0, seconds=1.0)
    run.trace, run.completed_in_window = trace, completed
    run.peaks = harness.peaks("TPU v5 lite")
    return run


def test_the_wide_cell_reports_the_fit_readers():
    names = {m["name"] for m in harness.metric_entries(SPEC, WIDE, True)}
    assert names == {"device_idle.fit", "traversal_roofline.fit",
                     "idle_unspanned.fit"}
    assert {m["name"] for m in harness.metric_entries(SPEC, WIDE, False)} \
        == {"fit_s", "setup_s"}


@pytest.mark.parametrize("chips,excerpt", [
    (4, "fit4_trace_excerpt.json"), (1, "fit50k_trace_excerpt.json")])
def test_device_idle_reads_the_mean_over_the_chips(chips, excerpt):
    tr = recorded(excerpt)
    lo, hi = tr.window
    assert len(tr.ops) == chips
    read = harness.reader("device_idle.fit")
    want = np.mean([brute_busy(ops, lo, hi) for ops in tr.ops.values()])
    assert read(traced_run(WIDE, tr)) == pytest.approx(
        100 * (1 - want / (hi - lo)), abs=0.05)
    # the loop op spans the whole excerpt; without it the gaps between
    # the step's ops show
    tr.ops = {k: [op for op in ops if not op[2].startswith("while")]
              for k, ops in tr.ops.items()}
    want = np.mean([brute_busy(ops, lo, hi) for ops in tr.ops.values()])
    got = read(traced_run(WIDE, tr))
    assert got == pytest.approx(100 * (1 - want / (hi - lo)), abs=0.05)
    assert 0 < got < 100


@pytest.mark.parametrize("cell,chips,excerpt,n,d", [
    ("spotify-fit50k", 4, "fit4_trace_excerpt.json", 50_000, 13),
    (WIDE, 1, "fit50k_trace_excerpt.json", 50_000, 768)])
def test_traversal_roofline_divides_the_problems_work_by_the_chips(
        cell, chips, excerpt, n, d):
    tr = recorded(excerpt)
    lo, hi = tr.window
    spent = np.mean([brute_busy(p, lo, hi) for p in tr.programs.values()])
    least = n * (n - 1) / 2 * 2 * d / 197e12 / chips   # compute binds
    got = harness.reader("traversal_roofline.fit")(
        traced_run(cell, tr, chips=chips))
    assert got == pytest.approx(100 * least / spent, rel=1e-3)


def test_idle_unspanned_fit_reads_the_span_summary_in_the_wide_cell():
    run = traced_run(WIDE, None)
    assert harness.reader("idle_unspanned.fit")(run) is None
    run.notes["spans"] = {"idle_unspanned": 11.5}
    assert harness.reader("idle_unspanned.fit")(run) == 11.5
