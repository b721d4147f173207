"""Production mesh construction.

A function, not a module-level constant: importing this module never touches
jax device state.  ``dryrun.py`` sets XLA_FLAGS for 512 host devices BEFORE
importing anything.
"""
from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "make_local_mesh", "mesh_axes"]


def _auto_mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_local_mesh(data: int | None = None, model: int = 1):
    """Small mesh over whatever devices exist (tests / CPU training)."""
    n = len(jax.devices())
    data = data or (n // model)
    return _auto_mesh((data, model), ("data", "model"))


def mesh_axes(mesh) -> tuple[str, ...]:
    return tuple(mesh.axis_names)


def batch_axes(mesh) -> tuple[str, ...]:
    """Axes that shard the batch: ('pod','data') when pods exist."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))
