"""Production mesh construction.

Kept as functions (never module-level constants) so importing this module
never touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* any jax
initialization, and smoke tests must keep seeing 1 device.

Target hardware: TPU v5e pods — 16x16 (256 chips) per pod; the multi-pod
mesh prepends a DCN "pod" axis (2 pods = 512 chips).
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType, Mesh


def make_mesh(shape: Sequence[int], axes: Sequence[str],
              devices: Optional[Sequence] = None) -> Mesh:
    """A mesh whose axes are all Auto: GSPMD propagates shardings and the
    rules' ``with_sharding_constraint`` hints steer it. (``jax.make_mesh``
    defaults to Explicit axes, which refuse those hints.)"""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model_axis: int = 1,
                   devices: Optional[Sequence] = None) -> Mesh:
    """(data, model) mesh over ``devices`` (default: every device)."""
    devices = list(devices) if devices is not None else jax.devices()
    data = max(len(devices) // model_axis, 1)
    return make_mesh((data, model_axis), ("data", "model"),
                     devices=devices[:data * model_axis])
