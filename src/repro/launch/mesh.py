"""Production mesh construction.

A function, not a module-level constant, so importing this module never
touches jax device state. Single pod: 16x16 = 256 chips ("data", "model");
multi-pod: 2x16x16 = 512 chips ("pod", "data", "model"). The dry-run
launcher sets XLA_FLAGS host-device count BEFORE any jax import.
"""
from __future__ import annotations

import jax


def _auto(ndim: int) -> tuple:
    # Auto axes: the model code places arrays with with_sharding_constraint,
    # which rejects the Explicit axes jax.make_mesh creates by default.
    return (jax.sharding.AxisType.Auto,) * ndim


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    import numpy as np
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices (set XLA_FLAGS=--xla_force_host_platform_"
            f"device_count=512 before importing jax); have {len(devices)}")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(shape)),
                         devices=devices[:n])


def make_local_mesh(model_axis: int = 1):
    """Mesh over whatever devices exist (tests / single host)."""
    n = len(jax.devices())
    return jax.make_mesh((n // model_axis, model_axis), ("data", "model"),
                         axis_types=_auto(2))


def dp_size(mesh) -> int:
    n = 1
    for a in ("pod", "data"):
        if a in mesh.axis_names:
            n *= mesh.shape[a]
    return n
