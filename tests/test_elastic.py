"""Elastic re-mesh: a checkpoint written on one topology restores and
trains on another (checkpoints hold unsharded logical tensors)."""
import subprocess
import sys
import textwrap

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import shutil
    import jax, jax.numpy as jnp
    from repro.checkpoint import ckpt
    from repro.configs import smoke_config
    from repro.configs.base import ShapeConfig, TrainConfig
    from repro.data.tokens import make_batch, input_specs
    from repro.launch.shardspecs import batch_shardings, state_shardings
    from repro.models import sharding
    from repro.train import steps as S

    cfg = smoke_config("phi3-mini-3.8b")
    tc = TrainConfig(lr=1e-3)
    shape = ShapeConfig("t", 32, 8, "train")
    ckdir = "/tmp/repro_elastic_ckpt"
    shutil.rmtree(ckdir, ignore_errors=True)

    # phase 1: "train" on a 1-device mesh and checkpoint
    state = S.init_state(cfg, tc, jax.random.PRNGKey(0))
    step1 = jax.jit(S.build_train_step(cfg, tc))
    batch = {k: jnp.asarray(v) for k, v in make_batch(cfg, shape).items()}
    state, m1 = step1(state, batch)
    ckpt.save(ckdir, 1, state)

    # phase 2: restore onto a 4x2 mesh (different topology) and continue
    mesh = jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    sharding.set_mesh(mesh)
    template = jax.eval_shape(
        lambda: S.init_state(cfg, tc, jax.random.PRNGKey(0)))
    restored, man = ckpt.restore(ckdir, template)
    assert man["step"] == 1
    st_sh = state_shardings(restored, mesh)
    restored = jax.device_put(restored, st_sh)
    fn = jax.jit(S.build_train_step(cfg, tc),
                 in_shardings=(st_sh,
                               batch_shardings(cfg, mesh,
                                               input_specs(cfg, shape))))
    with mesh:
        state2, m2 = fn(restored, batch)
    assert jnp.isfinite(m2["loss"]), m2
    # the restored step must see the same loss landscape: one more step
    # from the same state on either mesh starts from identical params
    print("ELASTIC_OK", float(m2["loss"]))
""")


def test_elastic_remesh_subprocess():
    r = subprocess.run([sys.executable, "-c", SCRIPT],
                       capture_output=True, text=True, timeout=500,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            "JAX_PLATFORMS": "cpu"})
    assert "ELASTIC_OK" in r.stdout, r.stderr[-3000:]
