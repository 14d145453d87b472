"""Production mesh construction.

Defined as FUNCTIONS (not module-level constants) so importing this module
never touches jax device state — the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before first jax init,
and smoke tests / benches must keep seeing 1 device.

Every axis is `AxisType.Auto`: the planner's rules are sharding
constraints that GSPMD propagates (`jax.make_mesh` would otherwise make
the axes Explicit and demand an output sharding for every gather).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType


def _auto_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod single-pod, or 2x16x16 = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_mesh(shape: Tuple[int, ...],
              axes: Optional[Tuple[str, ...]] = None):
    """Arbitrary mesh for tests/small runs, e.g. ((2, 2), ('data','model'))."""
    if axes is None:
        axes = ("pod", "data", "model")[-len(shape):]
    return _auto_mesh(tuple(shape), tuple(axes))


def single_device_mesh():
    return _auto_mesh((1, 1), ("data", "model"))


def mesh_devices(mesh) -> int:
    n = 1
    for s in mesh.devices.shape:
        n *= s
    return n
