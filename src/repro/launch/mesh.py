"""Production mesh construction. A FUNCTION, not a module constant, so
importing this module never touches jax device state."""
from __future__ import annotations

import jax


def _make(shape: tuple, axes: tuple):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make(shape, axes)


def make_mesh(shape: tuple, axes: tuple):
    """Elastic variant: any (shape, axes); used by tests and small runs."""
    return _make(shape, axes)
