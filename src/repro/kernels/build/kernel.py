"""Pallas TPU kernel: tiled partial-key window gather for the tree build.

Stage 3 of the pipeline (bulk build, §5.3) spends its per-entry time on one
primitive: slice ``pk`` bits out of each entry's full key starting at a
per-entry bit position (the bit after the entry's distinction bit — paper
option C.b, the partial key is read out of the record's own key).  The jnp
realization (`repro.core.btree._slice_bits`) is a pair of
``take_along_axis`` word gathers; this kernel is the tiled, planes-native
variant:

* entries stream through VMEM in ``tile``-lane blocks, full keys as word
  planes (one (W, tile) block per grid step) with the start positions as a
  (1, tile) int32 plane alongside;
* the per-lane word selection is branch-free: each of the ``W`` planes is
  selected into the (word, word+1) straddle pair with a lane-wide compare
  + select (W is 2–4 words; a compare/select pair per plane beats a lane
  gather on the VPU);
* the double-shift concatenation and the final ``32 - pk`` right shift are
  plain lane-wise uint32 ops.

Bit-for-bit identical to ``_slice_bits`` by construction (same clip, same
straddle semantics, same shift widths) — the build programs swap it in via
``build_btree(slice_fn=...)`` and the backend parity tests hold across the
substitution.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_TILE = 512


def _pk_window_kernel(n_words: int, pk: int, w_ref, s_ref, o_ref):
    """w_ref: (W, tile) key word planes; s_ref: (1, tile) int32 start bit
    positions; o_ref: (1, tile) uint32 pk-bit windows.

    Mirrors ``repro.core.btree._slice_bits``: clip the start into the key,
    read the straddling word pair (the second word is zero past the key
    end), shift-concatenate, keep the top ``pk`` bits.
    """
    start = jnp.clip(s_ref[0, :], 0, n_words * 32 - 1)
    wi = start // 32
    sh = (start % 32).astype(jnp.uint32)
    w0 = jnp.zeros(start.shape, jnp.uint32)
    w1 = jnp.zeros(start.shape, jnp.uint32)
    for w in range(n_words):
        plane = w_ref[w, :]
        w0 = jnp.where(wi == w, plane, w0)
        # wi + 1 == W selects nothing, leaving the zero fill — identical to
        # the oracle's where(wi + 1 < W, ..., 0)
        w1 = jnp.where(wi + 1 == w, plane, w1)
    hi = w0 << sh
    lo = jnp.where(sh == 0, jnp.uint32(0), w1 >> (jnp.uint32(32) - sh))
    o_ref[0, :] = (hi | lo) >> jnp.uint32(32 - pk)


@partial(jax.jit, static_argnames=("pk", "tile", "interpret"))
def pk_window_planes(
    word_planes: jnp.ndarray,
    starts: jnp.ndarray,
    pk: int,
    *,
    interpret: bool,
    tile: int = DEFAULT_TILE,
) -> jnp.ndarray:
    """(W, n) uint32 key word planes + (n,) int32 starts -> (n,) uint32
    pk-bit windows.  ``n`` must be a multiple of ``tile``."""
    w, n = word_planes.shape
    assert n % tile == 0, (word_planes.shape, tile)
    grid = (n // tile,)
    out = pl.pallas_call(
        partial(_pk_window_kernel, w, int(pk)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((w, tile), lambda i: (0, i)),
            pl.BlockSpec((1, tile), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, tile), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.uint32),
        interpret=interpret,
    )(word_planes, starts[None, :].astype(jnp.int32))
    return out[0]
