"""traversal_roofline.fit: the least time the exact Prim traversal's
problem needs on the cell's chips, over the device time of the programs
that run it.

The work is the problem's, not an implementation's (counts/traversal.py):
n(n-1)/2 pair distances at 2d flops each plus one read of X, so a change
of engine cannot push the share past 100%.  The programs are found by the
names below; the solo program also holds the seed pivot scan, so the
share is a lower bound.  Which roof binds is printed on stderr.
"""
import sys
from pathlib import Path

import harness
import tracereduce

#: XLA program names of the exact traversal: the solo engine
#: (core/vat.py::vat_matrix_free) and its sharded twin
#: (core/distributed.py::vat_matrix_free_sharded, a jitted shard_map).
PROGRAMS = ("jit_vat_matrix_free", "jit_shard_map", "jit__flash_shard")

_counts = harness.load_module(Path(__file__).resolve().parents[1]
                              / "counts" / "traversal.py")


def read(run):
    if run.trace is None or not run.completed_in_window:
        return None
    spent = tracereduce.program_seconds(run.trace, PROGRAMS)
    if not spent:
        return None
    cfg = run.cell.config
    flops, nbytes = _counts.work(cfg["rows"], cfg["fields"])
    t_flops = flops / run.peaks["flops_per_s"]
    t_bytes = nbytes / run.peaks["bytes_per_s"]
    least = max(t_flops, t_bytes) / run.cell.chips
    print(f"traversal roofline: {'compute' if t_flops >= t_bytes else 'memory'}"
          f" roof binds ({t_flops!r} s of flops, {t_bytes!r} s of bytes per"
          f" traversal); {spent!r} device s over {run.completed_in_window}"
          " traversals", file=sys.stderr)
    return 100.0 * least * run.completed_in_window / spent
