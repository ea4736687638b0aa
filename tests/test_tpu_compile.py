"""Compile every Pallas kernel for a described TPU v5e, at real sizes.

No chip is needed: the TPU compiler is installed, and it compiles for a
chip that is described, not attached.  Interpret mode (what the rest of
the suite runs on the CPU) accepts block shapes, value-level dynamic
slices and unaligned lane stores that the TPU compiler refuses, so this
file is what pins "the kernel compiles for the chip".  Each case
compiles with ``interpret=False`` and asserts the Mosaic kernel
(``tpu_custom_call``) is in the compiled program.

The topology is described inside a module-scoped fixture (never at
import time), and every test here skips from that fixture when no v5e
can be described.
"""
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.kernels import ivat_update, knn_graph, pairwise_dist
from repro.kernels import prim_persist, prim_stream, prim_update
from repro.kernels.ref import METRICS


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache; keep these compiles out of it
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", cached)


@pytest.fixture(scope="module")
def spec(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return make


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


# ------------------------------------------------------ pairwise tiles ----

@pytest.mark.parametrize("metric,form,n", [
    ("euclidean", "gram", 8192), ("sqeuclidean", "gram", 8192),
    ("cosine", "gram", 8192),
    ("manhattan", "gram", 2048), ("euclidean", "direct", 2048),
    ("sqeuclidean", "direct", 2048),
])
def test_pairwise_tile_compiles(spec, metric, form, n):
    _compile(lambda X: pairwise_dist.pairwise_dist_pallas(
        X, metric=metric, form=form), spec((n, 128)))


@pytest.mark.parametrize("metric,form", [("euclidean", "gram"),
                                         ("manhattan", "gram"),
                                         ("euclidean", "direct")])
def test_pairwise_batch_tile_compiles(spec, metric, form):
    _compile(lambda X: pairwise_dist.pairwise_dist_pallas_batch(
        X, metric=metric, form=form), spec((4, 1024, 128)))


# ------------------------------------------------------- Prim kernels ----

def test_masked_argmin_compiles(spec):
    _compile(prim_update.masked_argmin_pallas, spec((8192,)),
             spec((8192,), jnp.bool_))


#: every metric in the Gram form, plus the direct form the numerics
#: policy picks for badly conditioned euclidean/sqeuclidean data
FORMS = ([(m, "gram") for m in METRICS]
         + [("euclidean", "direct"), ("sqeuclidean", "direct")])


@pytest.mark.parametrize("metric,form", FORMS)
def test_prim_stream_step_compiles(spec, metric, form):
    n = 8192
    _compile(lambda X, a, q, m, s: prim_stream.prim_stream_step_pallas(
        X, a, q, m, s, metric=metric, form=form), spec((n, 128)),
        spec((n,)), spec((), jnp.int32), spec((n,)), spec((n,), jnp.bool_))


def test_prim_frontier_step_compiles(spec):
    n = 8192
    _compile(prim_stream.prim_frontier_step_pallas, spec((n, 128)),
             spec((n,)), spec((128,)), spec(()), spec((n,)),
             spec((n,), jnp.bool_))


@pytest.mark.parametrize("form", ["gram", "direct"])
def test_prim_stream_step_batch_compiles(spec, form):
    b, n = 4, 8192
    _compile(lambda X, a, q, m, s: prim_stream.prim_stream_step_pallas_batch(
        X, a, q, m, s, form=form), spec((b, n, 128)), spec((b, n)),
        spec((b,), jnp.int32), spec((b, n)), spec((b, n), jnp.bool_))


@pytest.mark.parametrize("metric,form,block",
                         [(m, f, prim_stream.DEFAULT_BLOCK) for m, f in FORMS]
                         + [("euclidean", "gram", 200)])
def test_persist_megakernel_compiles_at_medium_n(spec, metric, form, block):
    """The Turbo megakernel at flashvat's auto ceiling (n = 50 000).  A
    block that is not a multiple of 128 is rounded up to whole 128-lane
    rows, so every tile's frontier slice stays lane-aligned."""
    n, d = 50_000, 128
    assert prim_persist._persist_block(n, block) % 128 == 0
    assert prim_persist.persist_supported(n, d, block=block)
    _compile(lambda X, a, i: prim_persist.prim_persist_pallas(
        X, a, i, metric=metric, form=form, block=block), spec((n, d)),
        spec((n,)), spec((), jnp.int32))


def test_persist_guard_admits_only_what_compiles(spec):
    """The VMEM guard's edge: (50 000, 1024) is admitted and compiles
    (cosine keeps the most temporaries); the next lane width is refused
    before the compiler would run out of VMEM."""
    n = 50_000
    assert prim_persist.persist_supported(n, 1024)
    assert not prim_persist.persist_supported(n, 1152)
    _compile(lambda X, a, i: prim_persist.prim_persist_pallas(
        X, a, i, metric="cosine"), spec((n, 1024)), spec((n,)),
        spec((), jnp.int32))


# ------------------------------------------------------- iVAT and kNN ----

def test_ivat_update_compiles_at_fused_ceiling(spec):
    n = ivat_update.MAX_FUSED_N
    _compile(ivat_update.ivat_from_vat_pallas, spec((n, n)))


@pytest.mark.parametrize("d", [128, 768])
@pytest.mark.parametrize("metric", METRICS)
def test_knn_fold_compiles(spec, metric, d):
    _compile(lambda X: knn_graph.knn_graph_pallas(X, k=15, metric=metric),
             spec((8192, d)))


# ------------------------------------------------- four-chip traversal ----

@pytest.mark.parametrize("use_pallas", [False, True])
def test_sharded_traversal_compiles_on_2x2(topo, monkeypatch, use_pallas):
    """The sharded exact engine at n = 50 000 over the four v5e chips:
    collectives present, and the Pallas step kernel in the program."""
    from repro.core import distributed
    from repro.kernels import ops
    # the CPU backend would pick interpret mode for a described chip
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    mesh = Mesh(np.array(topo.devices), ("data",))
    program = distributed._flash_sharded_program(
        mesh, "data", 50_000, "euclidean", use_pallas, 1024)
    x = jax.ShapeDtypeStruct((50_000, 128), jnp.float32,
                             sharding=NamedSharding(mesh, P("data", None)))
    txt = program.lower(x).compile().as_text()
    assert "all-gather" in txt or "all-reduce" in txt
    assert ("tpu_custom_call" in txt) == use_pallas
