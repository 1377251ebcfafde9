"""Mesh context: lets model code state sharding intent without importing
mesh machinery everywhere.

``use_mesh(mesh, data_axes, model_axis)`` installs the mesh; ``constrain``
then applies ``with_sharding_constraint`` with logical axis names resolved
to the installed mesh ("data" -> the (possibly composite) batch axes,
"model" -> the tensor-parallel axis).  Outside a mesh context every helper
is a no-op, so the same model code runs single-device (smoke tests) and on
the 512-chip dry-run mesh unchanged.
"""

from __future__ import annotations

import contextlib
import threading

import jax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P


_state = threading.local()


def current() -> tuple[Mesh, tuple, str] | None:
    return getattr(_state, "ctx", None)


@contextlib.contextmanager
def use_mesh(mesh: Mesh, data_axes=("data",), model_axis: str = "model"):
    prev = getattr(_state, "ctx", None)
    _state.ctx = (mesh, tuple(data_axes), model_axis)
    try:
        with jax.set_mesh(mesh):
            yield mesh
    finally:
        _state.ctx = prev


def _resolve(axis):
    ctx = current()
    if ctx is None:
        return None
    _, data_axes, model_axis = ctx
    if axis == "data":
        return data_axes if len(data_axes) > 1 else data_axes[0]
    if axis == "model":
        return model_axis
    return axis  # literal mesh axis name or None


def spec(*logical_axes) -> P:
    return P(*[_resolve(a) for a in logical_axes])


def constrain(x, *logical_axes):
    """with_sharding_constraint using logical axis names; no-op without mesh."""
    ctx = current()
    if ctx is None:
        return x
    mesh, _, _ = ctx
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec(*logical_axes)))


def named_sharding(*logical_axes) -> NamedSharding | None:
    ctx = current()
    if ctx is None:
        return None
    mesh, _, _ = ctx
    return NamedSharding(mesh, spec(*logical_axes))


def axis_size(logical: str) -> int:
    """Mesh extent of a logical axis (1 outside a mesh context)."""
    ctx = current()
    if ctx is None:
        return 1
    mesh, _, _ = ctx
    resolved = _resolve(logical)
    if resolved is None:
        return 1
    if isinstance(resolved, (tuple, list)):
        n = 1
        for a in resolved:
            n *= mesh.shape[a]
        return n
    return mesh.shape[resolved]
