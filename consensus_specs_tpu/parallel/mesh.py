"""Mesh construction helpers.

One place decides how devices are laid out; everything else takes a Mesh.
On real hardware the axis rides ICI; under
--xla_force_host_platform_device_count it rides host memory, which is how
the test suite and the driver's dry-run exercise multi-chip code paths
without a pod (SURVEY.md environment notes).
"""
from __future__ import annotations

import numpy as np
import jax
from jax.sharding import Mesh


def device_count() -> int:
    return len(jax.devices())


def get_mesh(n_devices: int | None = None,
             axis_name: str = "data") -> Mesh:
    devices = jax.devices()
    if n_devices is None:
        n_devices = len(devices)
    if len(devices) < n_devices:
        raise RuntimeError(
            f"need {n_devices} devices, have {len(devices)} "
            "(set --xla_force_host_platform_device_count)")
    return Mesh(np.array(devices[:n_devices]), axis_names=(axis_name,))
