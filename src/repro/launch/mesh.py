"""Production meshes.

Defined as functions (never module-level constants) so importing this module
never touches JAX device state.  Target: TPU v5e pods — 256 chips/pod in a
(data=16, model=16) layout; the multi-pod mesh adds a leading 'pod' axis
(2 x 256 = 512 chips) over DCN.
"""
from __future__ import annotations

import math

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    need = math.prod(shape)
    devices = jax.devices()
    if len(devices) < need:
        raise RuntimeError(
            f"mesh {shape} needs {need} devices, found {len(devices)} — the "
            "dry-run must set XLA_FLAGS=--xla_force_host_platform_device_count"
            "=512 before importing jax (see launch/dryrun.py)")
    return jax.make_mesh(shape, axes, devices=devices[:need],
                         axis_types=(AxisType.Auto,) * len(shape))


def make_debug_mesh(data: int = 1, model: int = 1) -> jax.sharding.Mesh:
    """Small mesh over however many (host) devices exist — tests only."""
    need = data * model
    return jax.make_mesh((data, model), ("data", "model"),
                         devices=jax.devices()[:need],
                         axis_types=(AxisType.Auto,) * 2)
