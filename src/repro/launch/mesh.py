"""Production mesh construction (assignment-mandated shapes)."""
from __future__ import annotations

import jax


def _auto(n_axes: int) -> tuple:
    return (jax.sharding.AxisType.Auto,) * n_axes


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_mesh_for(n_data: int, n_model: int, n_pod: int = 1):
    """Smaller meshes for subprocess SPMD tests and elastic resize."""
    if n_pod > 1:
        return jax.make_mesh((n_pod, n_data, n_model),
                             ("pod", "data", "model"), axis_types=_auto(3))
    return jax.make_mesh((n_data, n_model), ("data", "model"),
                         axis_types=_auto(2))
