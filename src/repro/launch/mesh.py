"""Production meshes.  A FUNCTION (not a module constant) so importing this
module never touches jax device state -- the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before first init."""

from __future__ import annotations

import math

import jax

__all__ = ["make_production_mesh", "POD_SHAPE", "MULTIPOD_SHAPE"]

POD_SHAPE = (16, 16)                    # 256 chips / pod
MULTIPOD_SHAPE = (2, 16, 16)            # 2 pods = 512 chips


def make_production_mesh(*, multi_pod: bool = False):
    shape = MULTIPOD_SHAPE if multi_pod else POD_SHAPE
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    need = math.prod(shape)
    devices = jax.devices()
    if len(devices) < need:
        raise RuntimeError(
            f"mesh {shape} needs {need} devices, found {len(devices)} -- "
            "did you forget XLA_FLAGS=--xla_force_host_platform_device_count=512 "
            "(set as the very first line of dryrun.py)?"
        )
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         devices=devices[:need])
