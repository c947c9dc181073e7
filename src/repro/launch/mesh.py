"""Production mesh definitions.

``make_production_mesh`` is a function (not a module-level constant) so that
importing this module never touches jax device state — required because the
dry-run launcher must set ``XLA_FLAGS`` before any jax initialization.
"""
from __future__ import annotations

import numpy as np

import jax
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16x16 = 256 chips, axes (data, model).
    Multi-pod: 2x16x16 = 512 chips, axes (pod, data, model).

    Uses the first prod(shape) devices, so a 512-host-device process can
    build both meshes."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices, have {len(devices)} — the dry-run launcher "
            "must set XLA_FLAGS=--xla_force_host_platform_device_count=512 "
            "before any jax import")
    dev = np.asarray(devices[:n]).reshape(shape)
    return Mesh(dev, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(n_data: int | None = None, n_model: int = 1):
    """A small mesh over whatever devices exist (tests / CPU smoke runs)."""
    n_dev = len(jax.devices())
    if n_data is None:
        n_data = n_dev // n_model
    return jax.make_mesh((n_data, n_model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def data_axes(mesh) -> tuple:
    """The batch-sharding axes of a mesh (('pod','data') when multi-pod)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))
