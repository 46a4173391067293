"""Mesh construction.

``make_production_mesh`` is a FUNCTION (importing this module never
touches jax device state): (16, 16) ("data", "model") single pod — 256
chips — or (2, 16, 16) ("pod", "data", "model") for the 2-pod / 512-chip
dry run. The "pod" axis is an outer data-parallel axis whose collectives
cross the inter-pod DCN links. Every axis is ``AxisType.Auto``.
"""
from __future__ import annotations

from typing import Tuple

import jax


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> jax.sharding.Mesh:
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def small_test_mesh(data: int = 2, model: int = 4) -> jax.sharding.Mesh:
    """CPU-host test mesh (requires xla_force_host_platform_device_count)."""
    return make_mesh((data, model), ("data", "model"))


def make_tp_mesh(tp: int) -> jax.sharding.Mesh:
    """1-D tensor-parallel mesh for sharded serving
    (``EngineConfig(mesh=make_tp_mesh(N))``): the first ``tp`` devices on
    one "tp" axis. Raises with an actionable message when the process
    does not hold enough devices (on a CPU host, set
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``)."""
    n = jax.device_count()
    if tp > n:
        raise ValueError(
            f"tp={tp} needs {tp} devices but this process has {n}; on a "
            "CPU host set XLA_FLAGS=--xla_force_host_platform_device_"
            f"count={tp} before jax initializes")
    return make_mesh((tp,), ("tp",))
