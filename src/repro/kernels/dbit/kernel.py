"""Pallas TPU kernel: adjacent-key distinction bit positions (paper §5.3).

After the compressed key sort, the bulk build needs D_i = D-bit(key_{i-1},
key_i) for every adjacent pair — an O(n) scan that the paper folds into
reconstruction (Remark 1).  Kernel: XOR the key planes against the
1-shifted planes, locate the first differing word with an unrolled
running-mask pass over the (few) word planes, and take ``clz`` of that word
— all lane-parallel over a VMEM tile of keys.

Inputs arrive as two plane blocks (current and previous rows) so each grid
step is self-contained; ops.py builds the shifted copy once.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.dbits import NO_DBIT

DEFAULT_TILE = 1024


def _dbit_kernel(n_words: int, a_ref, b_ref, o_ref):
    """a_ref, b_ref: (W, T) planes (prev and current rows); o_ref: (1, T) int32."""
    a = a_ref[...]
    b = b_ref[...]
    t = a.shape[1]
    pos = jnp.full((t,), NO_DBIT, jnp.int32)
    found = jnp.zeros((t,), jnp.bool_)
    for w in range(n_words):
        x = a[w] ^ b[w]
        nz = x != 0
        take = nz & (~found)
        clz = jax.lax.clz(x.astype(jnp.uint32)).astype(jnp.int32)
        pos = jnp.where(take, jnp.int32(w * 32) + clz, pos)
        found = found | nz
    o_ref[...] = pos[None, :]


@partial(jax.jit, static_argnames=("tile", "interpret"))
def dbit_planes(
    prev_planes: jnp.ndarray,
    cur_planes: jnp.ndarray,
    *,
    interpret: bool,
    tile: int = DEFAULT_TILE,
) -> jnp.ndarray:
    """(W, n) x2 -> (n,) int32 distinction bit positions (NO_DBIT if equal)."""
    w, n = prev_planes.shape
    assert n % tile == 0
    grid = (n // tile,)
    out = pl.pallas_call(
        partial(_dbit_kernel, w),
        grid=grid,
        in_specs=[
            pl.BlockSpec((w, tile), lambda i: (0, i)),
            pl.BlockSpec((w, tile), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, tile), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.int32),
        interpret=interpret,
    )(prev_planes, cur_planes)
    return out[0]
