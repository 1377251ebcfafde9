"""The one mesh constructor every module shares.

``jax.make_mesh`` gives its axes ``AxisType.Explicit`` unless told
otherwise; the shard_map programs and sharding rules here are written for
``Auto`` axes, so every mesh in the tree is built through this function.
"""

from __future__ import annotations

import jax

__all__ = ["make_mesh"]


def make_mesh(axis_shapes, axis_names) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with ``Auto`` axis types."""
    names = tuple(axis_names)
    return jax.make_mesh(
        tuple(axis_shapes), names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(names),
    )
