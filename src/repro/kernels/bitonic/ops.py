"""Jit'd public wrapper for the bitonic block-sort kernel."""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core.plancache import pad_tail

from .kernel import DEFAULT_BLOCK, bitonic_block_sort_planes

# Pad sentinel sorts last (all-ones key); mirrors distsort's convention.
_SENTINEL = np.uint32(0xFFFFFFFF)


def block_sort(
    words: jnp.ndarray,
    rids: jnp.ndarray,
    *,
    interpret: bool,
    block: int = DEFAULT_BLOCK,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Sort each ``block`` of rows of (n, W) keys + (n,) rid payload.

    Rows are padded with all-ones sentinel keys up to a block multiple via
    ``plancache.pad_tail`` (a cached fill constant + one
    ``dynamic_update_slice`` — no per-call concatenate; the pad sorts to
    the tail of the final block and is stripped).  Returns the
    block-sorted (n, W) keys and (n,) rids — the paper's Appendix step 3.1;
    feed the runs to a merge (``lax.sort`` or the distsort exchange).
    """
    n, w = words.shape
    total = n + ((-n) % block)
    planes = jnp.concatenate(
        [jnp.asarray(words, jnp.uint32).T, jnp.asarray(rids, jnp.uint32)[None, :]], axis=0
    )
    planes = pad_tail(planes, total, _SENTINEL, axis=1)
    out = bitonic_block_sort_planes(planes, n_key_words=w, block=block, interpret=interpret)
    return out[:w, :n].T, out[w, :n]
