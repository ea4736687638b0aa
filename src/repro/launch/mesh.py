"""Production mesh builders (functions, never module-level constants —
importing this module must not touch jax device state)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes: the installed JAX defaults to
    Explicit axes, under which ``with_sharding_constraint`` and the
    implicit gathers the model's sharding rules rely on are refused."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Whatever this host actually has — for tests and local runs."""
    n = len(jax.devices())
    assert n % model == 0
    return _auto_mesh((n // model, model), ("data", "model"))
