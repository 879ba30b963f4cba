"""Production mesh construction (function, not constant — importing this
module never touches jax device state)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "dp_axes", "model_axis_size"]


def make_production_mesh(*, multi_pod: bool = False):
    """TPU v5e production mesh: 16x16 = 256 chips/pod; 2 pods multi-pod.

    Axes: 'pod' (slow inter-pod DCN/ICI), 'data' (DP + FSDP), 'model' (TP/EP).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    # Auto axes: the policy's with_sharding_constraint hints refer to them
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def dp_axes(mesh) -> tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def model_axis_size(mesh) -> int:
    return mesh.shape.get("model", 1)
