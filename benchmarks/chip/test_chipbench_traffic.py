"""The chip benchmark's seeded inputs and its data-driven layout."""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
REPO = HERE.parents[1]
sys.path[:0] = [str(HERE), str(REPO / "src")]

import harness  # noqa: E402
import traffic  # noqa: E402


def table(seed: int):
    return traffic.table(harness.load_cell("spotify-fit50k").config, seed)


def test_same_seed_gives_the_same_data():
    seed = 4_294_967_311                     # past 32 bits
    assert np.array_equal(table(seed), table(seed))


def test_another_seed_gives_other_data_of_the_same_shape():
    a, b = table(11), table(12)
    assert a.shape == b.shape == (50_000, 13)
    assert not np.array_equal(a, b)


def test_library_table_is_standardized_and_unsorted():
    cell = harness.load_cell("spotify-fit50k")
    X = traffic.table(cell.config, 3)
    assert X.shape == (cell.config["rows"], cell.config["fields"])
    assert X.dtype == np.float32
    assert np.allclose(X.mean(0), 0.0, atol=1e-5)
    assert np.allclose(X.std(0), 1.0, atol=1e-4)


def test_a_new_workload_file_is_listed_without_code(tmp_path):
    dest = tmp_path / "chip"
    shutil.copytree(HERE, dest, ignore=shutil.ignore_patterns("__pycache__"))
    (dest / "workloads" / "dummy-cell.json").write_text(json.dumps(
        {"config": "spotify-audio", "traffic": "closed-loop", "chips": 1,
         "trace_seconds": 2, "limits": {}}))
    assert "dummy-cell" in harness.list_workloads(dest)
    assert "dummy-cell" not in harness.list_workloads(HERE)
    cell = harness.load_cell("dummy-cell", dest)
    assert cell.loop == "library" and cell.config["name"] == "spotify-audio"


def test_benchmark_json_names_files_the_harness_finds():
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    assert {w["name"] for w in spec["workloads"]} <= set(
        harness.list_workloads())
    for w in spec["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell.chips == w["chips"]
        assert cell.config["name"] == w["config"]
        for m in (harness.metric_entries(spec, w["name"], False)
                  + harness.metric_entries(spec, w["name"], True)):
            assert callable(harness.reader(m["name"]))
        assert harness.metric_entries(spec, w["name"], True)
    for c in spec["configs"]:
        assert json.loads((REPO / c["file"]).read_text())["name"] == \
            c["name"]
    assert harness.peaks("TPU v5 lite")["flops_per_s"] == 197e12
