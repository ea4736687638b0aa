"""knn_device_ms.audit: device milliseconds per audit of the approx
rung's kNN graph and Boruvka programs (core/approx_mst.py,
kernels/knn_graph.py), found by their XLA program names."""
import tracereduce

#: _cell_topk: one anchored cell's top-k; _boruvka_pass: one Boruvka
#: round; knn_graph_blocked / knn_graph_pallas: the exact-mode kNN.
PROGRAMS = ("jit__cell_topk", "jit__boruvka_pass", "jit_knn_graph_blocked",
            "jit_knn_graph_pallas")


def read(run):
    if run.trace is None or not run.completed_in_window:
        return None
    spent = tracereduce.program_seconds(run.trace, PROGRAMS)
    if spent is None:
        return None
    return 1e3 * spent / run.completed_in_window
