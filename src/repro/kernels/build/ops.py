"""Jit-friendly public wrapper for the pk-window gather kernel."""

from __future__ import annotations

import jax.numpy as jnp

from repro.core.plancache import pad_tail

from .kernel import DEFAULT_TILE, pk_window_planes


def pk_windows(
    words: jnp.ndarray,
    starts: jnp.ndarray,
    pk: int,
    *,
    interpret: bool,
    tile: int = DEFAULT_TILE,
) -> jnp.ndarray:
    """(m, W) uint32 keys + (m,) start bit positions -> (m,) uint32 windows.

    Pads the entry axis to a tile multiple via ``plancache.pad_tail``
    (pad starts are 0 — harmless garbage lanes, stripped before return;
    cached zero constants, no per-call concatenate), transposes to word
    planes, and runs the tiled kernel.  Drop-in for
    ``repro.core.btree._slice_bits`` when the window axis is 1-D: the
    build programs call it through ``slice_fn`` so it traces inside the
    cached build program.
    """
    m, w = words.shape
    total = m + ((-m) % tile)
    planes = pad_tail(jnp.asarray(words, jnp.uint32).T, total, 0, axis=1)
    starts = pad_tail(jnp.asarray(starts, jnp.int32), total, 0)
    out = pk_window_planes(planes, starts, int(pk), tile=tile, interpret=interpret)
    return out[:m]


def slice_fn(*, interpret: bool, tile: int = DEFAULT_TILE):
    """A ``build_btree(slice_fn=...)``-shaped closure over kernel options."""

    def fn(words, starts, pk):
        return pk_windows(words, starts, pk, tile=tile, interpret=interpret)

    return fn
