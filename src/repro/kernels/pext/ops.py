"""Jit'd public wrapper for the pext kernel (row-major in/out, padding)."""

from __future__ import annotations

import jax.numpy as jnp

from repro.core.compress import ExtractionPlan
from repro.core.plancache import pad_tail

from .kernel import DEFAULT_TILE, pext_planes


def pext(
    words: jnp.ndarray,
    plan: ExtractionPlan,
    *,
    interpret: bool,
    tile: int = DEFAULT_TILE,
) -> jnp.ndarray:
    """(n, W) uint32 keys -> (n, Wc) uint32 compressed keys.

    Pads the key axis to a tile multiple (``plancache.pad_tail``: cached
    zero constant + one ``dynamic_update_slice`` — this wrapper is called
    eagerly on the pallas extract path, so the pad must not allocate per
    call), runs the plane kernel, strips the padding.  A planes-native
    pipeline should call ``pext_planes`` directly and skip both
    transposes.
    """
    n, w = words.shape
    total = n + ((-n) % tile)
    planes = pad_tail(jnp.asarray(words, jnp.uint32).T, total, 0, axis=1)
    out = pext_planes(planes, plan, tile=tile, interpret=interpret)
    return out[:, :n].T
