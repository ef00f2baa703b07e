"""Production mesh construction.

Single pod: 16 x 16 = 256 chips, axes (data, model).
Multi-pod:  2 x 16 x 16 = 512 chips, axes (pod, data, model) — 'pod' is the
DCN-connected axis; FSDP never crosses it (weights are replicated per pod,
gradients cross it once per step via the hierarchical FMI reduction).

A function, not a module constant: importing this module must never touch
jax device state (the dry-run needs to set XLA_FLAGS first).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType



def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (tests/examples on CPU)."""
    n = len(jax.devices())
    if data * model > n:
        raise ValueError(f"need {data*model} devices, have {n}")
    return jax.make_mesh((data, model), ("data", "model"), axis_types=(AxisType.Auto,) * 2)


def mesh_shape_dict(mesh) -> dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))
