"""Mesh construction — every mesh in the repo is built here.

On jax 0.9, ``jax.make_mesh`` makes every axis ``AxisType.Explicit`` unless
told otherwise.  The code is written for Auto sharding (the compiler
propagates shardings and shard_map marks the manual regions), so
:func:`make_mesh` pins every axis to ``AxisType.Auto``.

Target hardware: TPU v5e pods — 256 chips/pod (16x16), 2 pods for the
multi-pod dry-run.  Defined as functions (never module-level constants) so
importing this module never touches jax device state.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

from ..core.qsdp import MeshSpec


def make_mesh(shape, axes, *, devices=None) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with every axis Auto (see the module docstring)."""
    shape, axes = tuple(shape), tuple(axes)
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def local_mesh_shape(n_devices: int | None = None) -> tuple[int, int]:
    """(data, model) over every device found: model=4 when the count is a
    multiple of 8 (the 8-device layout the examples were written for),
    otherwise pure FSDP over all devices — (4, 1) on a four-chip host."""
    n = len(jax.devices()) if n_devices is None else n_devices
    tp = 4 if n % 8 == 0 else 1
    return n // tp, tp


def make_production_mesh(*, multi_pod: bool = False):
    ms = make_mesh_spec(multi_pod=multi_pod)
    return make_mesh(ms.shape, ms.axes)


def make_mesh_spec(*, multi_pod: bool = False) -> MeshSpec:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return MeshSpec(axes=axes, shape=shape)


def make_small_mesh(data: int = 2, model: int = 4):
    """Test/CI mesh (requires xla_force_host_platform_device_count >= d*m)."""
    return make_mesh((data, model), ("data", "model"))


def make_small_spec(data: int = 2, model: int = 4) -> MeshSpec:
    return MeshSpec(axes=("data", "model"), shape=(data, model))
