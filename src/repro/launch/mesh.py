"""Production mesh definitions.

IMPORTANT: functions, not module-level constants — importing this module must
never touch jax device state (the dry-run sets XLA_FLAGS before first init).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 single-pod (256 chips) or 2×16×16 multi-pod (512 chips).

    Axes: ("pod",) "data", "model" — DP over pod+data, TP/EP over model.
    The SpGEMM grid maps data→rows, model→cols, pod→layers
    (core.grid.grid_from_mesh).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_debug_mesh(data: int = 2, model: int = 2, pod: int = 0):
    """Small host-device mesh for tests/examples (needs XLA host-device flag)."""
    if pod:
        return jax.make_mesh(
            (pod, data, model), ("pod", "data", "model"),
            axis_types=(AxisType.Auto,) * 3,
        )
    return jax.make_mesh(
        (data, model), ("data", "model"), axis_types=(AxisType.Auto,) * 2
    )
