"""Thin helpers over the jax sharding API, so call sites stay short."""

from __future__ import annotations

from typing import Optional, Sequence

import jax


def get_abstract_mesh() -> Optional[object]:
    """The ambient mesh sharding constraints should resolve against."""
    return jax.sharding.get_abstract_mesh()


def set_mesh(mesh):
    """Context manager making ``mesh`` ambient for jit'd sharding."""
    return jax.set_mesh(mesh)


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str]):
    """``jax.make_mesh`` with auto axis types."""
    names = tuple(axis_names)
    return jax.make_mesh(tuple(axis_shapes), names,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(names))


def abstract_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str]):
    """Device-less mesh for planning shardings without real hardware."""
    return jax.sharding.AbstractMesh(tuple(axis_shapes), tuple(axis_names))
