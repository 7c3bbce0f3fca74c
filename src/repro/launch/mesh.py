"""Production mesh construction.

IMPORTANT: functions only — importing this module never touches jax device
state.  The dry-run sets XLA_FLAGS=--xla_force_host_platform_device_count=512
*before* any jax import (see dryrun.py's first two lines); smoke tests and
benchmarks import jax normally and see 1 device.
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto`` (GSPMD-propagated
    shardings, the semantics the sharding hints and shard_map bodies
    here are written for)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16x16 = 256 chips (data, model).
    Multi-pod: 2 pods x 256 = 512 chips (pod, data, model); the pod axis
    is the DCN/ICI-superlink dimension (DP across pods by default, PP
    optional — see launch/train.py)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(n_data: int = 2, n_model: int = 2, n_pod: int = 0):
    """Small mesh for subprocess tests (8 fake devices)."""
    if n_pod:
        return make_mesh((n_pod, n_data, n_model), ("pod", "data", "model"))
    return make_mesh((n_data, n_model), ("data", "model"))


def chips(mesh) -> int:
    n = 1
    for s in mesh.devices.shape:
        n *= s
    return n
