"""The benchmark's cells cut to sizes a CPU test run can hold.

``make(dest)`` copies this directory to ``dest`` and shrinks its tables:
the same loop, rungs, metrics and limits, at a few thousand rows.
The tests drive whole runs through ``harness.execute`` on that copy.
"""
from __future__ import annotations

import json
import shutil
from pathlib import Path

HERE = Path(__file__).resolve().parent

#: config -> (rows, fields, the method auto picks at the full size)
SIZES = {"spotify-audio": (1500, 13, "flashvat"),
         "acl-embed-768": (4000, 32, "approx")}
#: limits read at these sizes on a CPU: the sound runs' numbers lie well
#: under them, and the control's (``control.lowered("high")``) above
SMALL_LIMITS = {"image_err": 3e-6, "order_gap": 4e-5, "order_excess": 1e-2,
                "order_bound": 0.0}


def make(dest: Path) -> Path:
    dest = Path(dest)
    shutil.copytree(HERE, dest, ignore=shutil.ignore_patterns(
        "__pycache__", "test_*.py"))
    for name, (rows, fields, method) in SIZES.items():
        p = dest / "configs" / f"{name}.json"
        c = json.loads(p.read_text())
        c["rows"], c["fields"] = rows, fields
        c["fit"]["method"] = method
        p.write_text(json.dumps(c))
    for p in (dest / "workloads").glob("*.json"):
        w = json.loads(p.read_text())
        w["limits"] = {k: SMALL_LIMITS[k] for k in w["limits"]}
        p.write_text(json.dumps(w))
    return dest
