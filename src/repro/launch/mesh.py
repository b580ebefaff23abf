"""Production mesh construction.

IMPORTANT: functions only -- importing this module never touches jax
device state. The dry-run entrypoint sets the 512-device stub flag
before importing anything.
"""
from __future__ import annotations

import math

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 (one v5e pod, 256 chips) or 2x16x16 (two pods, 512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = math.prod(shape)
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, found {len(devices)} "
            "(dry-run must set --xla_force_host_platform_device_count=512)"
        )
    return jax.make_mesh(
        shape, axes, devices=devices[:n],
        axis_types=(AxisType.Auto,) * len(axes),
    )


def make_mesh(shape, axes):
    """Arbitrary small mesh for tests (e.g. (2, 4) on 8 stub devices)."""
    n = math.prod(shape)
    return jax.make_mesh(
        shape, axes, devices=jax.devices()[:n],
        axis_types=(AxisType.Auto,) * len(axes),
    )
