"""Fast-VAT core: the paper's contribution as composable JAX modules.

Module map: README.md (architecture) and docs/scaling.md (the
vat -> svat -> bigvat -> dvat -> streaming ladder); the user-facing
facade with automatic method selection is ``repro.api.FastVAT``.
"""
from repro.core.vat import (vat, vat_batch, vat_batch_from_dist,
                            vat_from_dist, vat_matrix_free,
                            vat_matrix_free_batch, vat_order, reorder,
                            VATResult, FlashVATResult,
                            block_structure_score)
from repro.core.ivat import (ivat, ivat_batch, ivat_batch_from_dist,
                             ivat_batch_from_vat, ivat_from_vat)
from repro.core.svat import svat, maximin_sample, SVATResult
from repro.core.hopkins import hopkins
from repro.core.distributed import (dvat, pairwise_dist_sharded,
                                    vat_matrix_free_sharded, DVATResult)
from repro.core.bigvat import bigvat, BigVATResult, nearest_prototype_assign
from repro.core.approx_mst import (approx_vat, boruvka_mst, knn_graph_anchored,
                                   mst_vat_order, ApproxStats,
                                   ApproxVATResult, MSTEdges)
from repro.core.cluster import kmeans, dbscan, adjusted_rand_index, pca

_DIAG_NAMES = ("activation_report", "embedding_tendency", "router_tendency",
               "TendencyReport")


def __getattr__(name):
    # Lazy: diagnostics now lives in repro.monitor.probes, which itself
    # imports repro.core primitives — an eager import here would cycle.
    if name in _DIAG_NAMES:
        from repro.core import diagnostics
        return getattr(diagnostics, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")

__all__ = [
    "vat", "vat_batch", "vat_batch_from_dist", "vat_from_dist",
    "vat_matrix_free", "vat_matrix_free_batch", "vat_order", "reorder",
    "VATResult", "FlashVATResult",
    "block_structure_score", "ivat", "ivat_batch", "ivat_batch_from_dist",
    "ivat_batch_from_vat", "ivat_from_vat", "svat",
    "maximin_sample", "SVATResult", "hopkins",
    "dvat", "pairwise_dist_sharded", "vat_matrix_free_sharded", "DVATResult",
    "bigvat", "BigVATResult", "nearest_prototype_assign",
    "approx_vat", "boruvka_mst", "knn_graph_anchored", "mst_vat_order",
    "ApproxStats", "ApproxVATResult", "MSTEdges",
    "activation_report",
    "embedding_tendency", "router_tendency", "TendencyReport",
]
from repro.core.streaming import StreamingVAT
__all__.append("StreamingVAT")
from repro.core.tsne import tsne
__all__.append("tsne")
