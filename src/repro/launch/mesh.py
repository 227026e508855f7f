"""Production mesh construction (function, not module constant — see
the dry-run contract: importing this module must not touch device
state)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes: the train step places data
    with ``with_sharding_constraint``, which refuses the Explicit axes
    ``make_mesh`` defaults to."""
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def data_axes(multi_pod: bool):
    """Axes that carry batch parallelism (pod stays pure-DP so the only
    cross-pod traffic is the per-step gradient reduce)."""
    return ("pod", "data") if multi_pod else ("data",)


def make_host_mesh(num_devices: int | None = None):
    """Small CPU mesh for tests/examples: (1, N) data×model."""
    n = num_devices or len(jax.devices())
    return _auto_mesh((n, 1), ("data", "model"))
