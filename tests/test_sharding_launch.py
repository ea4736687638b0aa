"""Sharding rules, mesh builders, input specs, and a reduced-mesh dry-run."""
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import ARCHS, SHAPES, get_config
from repro.data.tokens import input_specs
from repro.models import sharding as SH


@pytest.fixture()
def mesh16():
    # shape-only stand-in mesh: 1 real device but we only test spec logic
    return jax.make_mesh((1, 1), ("data", "model"))


class FakeMesh:
    """Spec-rule testing double with arbitrary axis sizes."""
    def __init__(self, sizes):
        self.axis_names = tuple(sizes)
        import numpy as np
        self.devices = np.empty(tuple(sizes.values()), object)


def test_spec_rules_tp_fsdp():
    mesh = FakeMesh({"data": 16, "model": 16})
    s = SH.spec_for("layers/wq", (32, 4096, 4096), mesh)
    assert s == P(None, "data", "model")
    s = SH.spec_for("layers/wo", (32, 4096, 4096), mesh)
    assert s == P(None, "model", "data")
    s = SH.spec_for("layers/e_up", (32, 16, 4096, 6400), mesh)
    assert s == P(None, "model", "data", None)
    s = SH.spec_for("embed", (32000, 4096), mesh)
    assert s == P("model", "data")


def test_spec_divisibility_fallback():
    mesh = FakeMesh({"data": 16, "model": 16})
    # whisper vocab 51866 is not divisible by 16 -> unsharded vocab dim
    s = SH.spec_for("embed", (51866, 1280), mesh)
    assert s == P(None, "data")
    # odd inner dim entirely unshardable
    s = SH.spec_for("layers/wq", (2, 897, 1283), mesh)
    assert s == P(None, None, None)
    # norms replicated
    s = SH.spec_for("layers/ln1", (32, 4096), mesh)
    assert s == P(None, None)


def test_hint_noop_without_mesh():
    SH.set_mesh(None)
    x = jnp.ones((4, 4))
    assert SH.hint(x, "dp", "model") is x


def test_make_production_mesh_requires_512_devices():
    # on this 1-device process the production mesh must fail loudly,
    # proving dryrun's forced device count is what makes it work
    from repro.launch.mesh import make_production_mesh
    if len(jax.devices()) < 256:
        with pytest.raises(Exception):
            make_production_mesh()


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_input_specs_are_abstract(arch):
    cfg = get_config(arch)
    specs = input_specs(cfg, SHAPES["train_4k"])
    for v in specs.values():
        assert isinstance(v, jax.ShapeDtypeStruct)


DRYRUN_SMALL = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    from repro.configs import smoke_config
    from repro.configs.base import ShapeConfig, TrainConfig
    from repro.data.tokens import input_specs
    from repro.launch.shardspecs import batch_shardings, state_shardings
    from repro.models import sharding
    from repro.train import steps as S

    cfg = smoke_config("phi3.5-moe-42b-a6.6b").replace(
        d_model=64, n_heads=4, n_kv_heads=4, head_dim=16)
    shape = ShapeConfig("t", 64, 8, "train")
    mesh = jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    sharding.set_mesh(mesh)
    tc = TrainConfig()
    specs = input_specs(cfg, shape)
    state_shape = jax.eval_shape(
        lambda: S.init_state(cfg, tc, jax.random.PRNGKey(0), jnp.float32))
    fn = jax.jit(S.build_train_step(cfg, tc),
                 in_shardings=(state_shardings(state_shape, mesh),
                               batch_shardings(cfg, mesh, specs)),
                 donate_argnums=(0,))
    with mesh:
        compiled = fn.lower(state_shape, specs).compile()
    txt = compiled.as_text()
    assert any(k in txt for k in ("all-reduce", "all-gather")), "no collectives?"
    ca = compiled.cost_analysis()   # dict (jax >= 0.5) or [dict] (0.4.x)
    ca = ca[0] if isinstance(ca, (list, tuple)) else ca
    print("SMALL_DRYRUN_OK", ca.get("flops"))
""")


def test_small_mesh_dryrun_subprocess():
    r = subprocess.run([sys.executable, "-c", DRYRUN_SMALL],
                       capture_output=True, text=True, timeout=500,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            "JAX_PLATFORMS": "cpu"})
    assert "SMALL_DRYRUN_OK" in r.stdout, r.stderr[-3000:]
