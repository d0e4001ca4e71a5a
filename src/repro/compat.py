"""The one mesh factory that pins a jax default.

``jax.make_mesh`` types new mesh axes ``Explicit``; this repo's meshes
are ``Auto``-typed (sharding propagated by the compiler), so every
concrete mesh is built here.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """``jax.make_mesh`` with ``Auto`` axis types."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))
