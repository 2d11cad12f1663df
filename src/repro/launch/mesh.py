"""Production mesh construction (functions, never module-level constants —
importing this module must not touch jax device state)."""
from __future__ import annotations

from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh


def _auto_mesh(shape: tuple, axes: tuple) -> Mesh:
    # Auto axes: the model code places values with with_sharding_constraint,
    # which jax.make_mesh's default Explicit axes refuse
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod (TPU v5e); 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh():
    """Single-device mesh for CPU smoke runs."""
    return _auto_mesh((1, 1), ("data", "model"))


def make_serving_mesh(n_devices: Optional[int] = None, *,
                      axis: str = "data") -> Mesh:
    """1-D data-parallel mesh for the batched serving stack (DESIGN.md §6).

    The batch (document) axis of every ``BatchedJitEngine`` dispatch is
    sharded over this mesh's single ``axis``; weights replicate. Defaults to
    every visible device so the same call is device-count-agnostic across a
    laptop (1 CPU device), CI with forced host devices
    (``XLA_FLAGS=--xla_force_host_platform_device_count=4``) and a real
    accelerator ring. Pass ``n_devices`` to use a prefix of the device list
    (the sharded-parity tests pin mesh sizes 1/2/4 this way).
    """
    devs = jax.devices()
    k = len(devs) if n_devices is None else int(n_devices)
    if not 1 <= k <= len(devs):
        raise ValueError(
            f"serving mesh of {k} devices, but only {len(devs)} visible")
    return Mesh(np.asarray(devs[:k]), (axis,))
