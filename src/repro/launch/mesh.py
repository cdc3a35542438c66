"""Production meshes.

Defined as FUNCTIONS (never module-level constants) so importing this
module never touches jax device state — the dry-run pins the device count
via XLA_FLAGS before first jax init; everything else sees the real
topology.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import AxisType


def _auto(n: int) -> Tuple[AxisType, ...]:
    # the steps place arrays with sharding constraints, which need Auto
    # axes (``jax.make_mesh`` defaults to Explicit ones)
    return (AxisType.Auto,) * n


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, _auto(len(axes)))


def make_host_mesh(model_parallel: int = 1,
                   devices: Optional[Sequence] = None):
    """Whatever this host actually has (or the given ``devices``) — used
    by smoke tests/examples."""
    devices = list(devices or jax.devices())
    n = len(devices)
    mp = max(1, min(model_parallel, n))
    return jax.make_mesh((n // mp, mp), ("data", "model"), _auto(2),
                         devices=devices)


def mesh_info(mesh) -> Tuple[int, dict]:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return int(mesh.devices.size), sizes
