"""Production mesh construction. A FUNCTION (not a module constant) so that
importing this module never touches jax device state.

Single pod:  (16, 16)      axes ("data", "model")   = 256 chips (one v5e pod)
Multi-pod:   (2, 16, 16)   axes ("pod", "data", "model") = 512 chips
"""
from __future__ import annotations

import math
from typing import Optional, Sequence, Tuple


def make_mesh_shape(shape: Tuple[int, ...], axes: Tuple[str, ...],
                    devices: Optional[Sequence] = None):
    """Mesh over the first prod(shape) devices. Every axis is `Auto`: the
    sharding rules in `dist.sharding` place params and batches through
    jit in/out shardings and GSPMD propagates the rest (an `Explicit` axis,
    `jax.make_mesh`'s default, would demand an out-sharding on every gather
    of a sharded table, e.g. the embedding lookup)."""
    import jax
    from jax.sharding import AxisType

    n = math.prod(shape)
    devs = list(devices) if devices is not None else list(jax.devices())
    if len(devs) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, have {len(devs)} — the dry-run "
            "sets XLA_FLAGS=--xla_force_host_platform_device_count=512 before "
            "importing jax")
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(axes),
                         devices=devs[:n])


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh_shape(shape, axes)


def make_test_mesh(data: int = 1, model: int = 1):
    """Tiny mesh for CPU tests (1 device by default)."""
    return make_mesh_shape((data, model), ("data", "model"))
