"""Production meshes (v5e): single-pod 16x16 and 2-pod 2x16x16.

A function, not a module constant: importing this module must never touch
jax device state (the dry-run sets XLA_FLAGS before first jax init).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_debug_mesh(n_data: int = 2, n_model: int = 2):
    """Small host-device mesh for sharding unit tests (needs
    --xla_force_host_platform_device_count >= n_data*n_model)."""
    return jax.make_mesh((n_data, n_model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


LANE_AXIS = "lane"


def make_lane_mesh(n_lanes: int | None = None, *, devices=None):
    """1-D ``lane`` mesh for the lane-sharded cortex engine: side-agent
    lanes are split over this axis, main-stream state replicates. Defaults
    to every visible device (force more on CPU with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``). The axis is
    Auto: the engine places its state with explicit shardings and lets
    GSPMD propagate the rest (``jax.make_mesh`` defaults to Explicit axes,
    under which in-place cache updates in prefill are refused)."""
    devs = list(devices) if devices is not None else jax.devices()
    n = len(devs) if n_lanes is None else n_lanes
    if n > len(devs):
        raise ValueError(f"make_lane_mesh: {n} lanes > {len(devs)} devices")
    return jax.make_mesh((n,), (LANE_AXIS,), devices=devs[:n],
                         axis_types=(AxisType.Auto,))


def lane_axis(mesh) -> str | None:
    """The lane axis name when ``mesh`` carries one, else None."""
    return LANE_AXIS if mesh is not None and LANE_AXIS in mesh.axis_names else None


def data_axes(mesh) -> tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def model_axis(mesh) -> str:
    return "model"
