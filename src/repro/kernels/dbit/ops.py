"""Jit'd public wrapper for the adjacent-dbit kernel."""

from __future__ import annotations

import jax.numpy as jnp

from repro.core.plancache import pad_tail

from .kernel import DEFAULT_TILE, dbit_planes


def adjacent_dbits(
    sorted_words: jnp.ndarray, *, interpret: bool, tile: int = DEFAULT_TILE
) -> jnp.ndarray:
    """(n, W) sorted keys -> (n-1,) adjacent distinction bit positions.

    The tile pad rides ``plancache.pad_tail`` (cached zero constants, no
    per-call concatenate); pad columns are equal in both operands, so
    their positions are garbage that the ``[:m]`` slice strips.
    """
    n, w = sorted_words.shape
    planes = jnp.asarray(sorted_words, jnp.uint32).T  # (W, n)
    m = n - 1
    total = m + ((-m) % tile)
    prev = pad_tail(planes[:, : n - 1], total, 0, axis=1)
    cur = pad_tail(planes[:, 1:], total, 0, axis=1)
    out = dbit_planes(prev, cur, tile=tile, interpret=interpret)
    return out[:m]
