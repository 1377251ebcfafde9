"""Pallas TPU kernel: compressed-key bit extraction (the paper's PEXT step).

CPU version: one BMI ``PEXT`` per 8-byte mask word + shift/OR concatenation
(paper §5.1, Figure 8).  TPU adaptation: there is no bit-extract unit, but
the D-bitmap is metadata fixed at reconstruction time, so we compile it into
a static shift/mask schedule over **word planes**:

  layout:  keys as (W, n) uint32 planes — the key axis is the 128-lane axis,
           so every scheduled bit op is amortized over a full 8x128 vector
           register tile;
  per output bit b:  out[dw] |= ((in[sw] >> ss) & 1) << ds      (all lanes)

The schedule costs ~3 VPU ops per extracted bit per 1024-lane tile — the
MXU/VPU-idiomatic equivalent of PEXT's 1 cycle per 64-bit word, and it keeps
the whole tile in VMEM for the downstream sort.

Grid: 1-D over tiles of the key axis.  BlockSpec pins each (W, T) input
tile and (Wc, T) output tile in VMEM; W, Wc are sublane-sized (<= 128 words
for 512-byte keys), T defaults to 1024 lanes.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.compress import ExtractionPlan

DEFAULT_TILE = 1024


def _pext_kernel(plan: ExtractionPlan, x_ref, o_ref):
    """x_ref: (W, T) uint32 planes; o_ref: (Wc, T) uint32 planes."""
    x = x_ref[...]
    t = x.shape[1]
    out = [jnp.zeros((t,), jnp.uint32) for _ in range(plan.n_words_out)]
    for b in range(plan.n_bits):
        sw, ss = plan.src_word[b], plan.src_shift[b]
        dw, ds = plan.dst(b)
        bit = (x[sw, :] >> jnp.uint32(ss)) & jnp.uint32(1)
        out[dw] = out[dw] | (bit << jnp.uint32(ds))
    o_ref[...] = jnp.stack(out, axis=0)


@partial(jax.jit, static_argnames=("plan", "tile", "interpret"))
def pext_planes(
    planes: jnp.ndarray,
    plan: ExtractionPlan,
    *,
    interpret: bool,
    tile: int = DEFAULT_TILE,
) -> jnp.ndarray:
    """(W, n) uint32 word planes -> (Wc, n) compressed word planes.

    ``n`` must be a multiple of ``tile`` (ops.py pads).  ``interpret=True``
    runs the kernel body under the Pallas interpreter (CPU tests); the TPU
    path passes ``False``.
    """
    w, n = planes.shape
    assert n % tile == 0, (n, tile)
    grid = (n // tile,)
    return pl.pallas_call(
        partial(_pext_kernel, plan),
        grid=grid,
        in_specs=[pl.BlockSpec((w, tile), lambda i: (0, i))],
        out_specs=pl.BlockSpec((plan.n_words_out, tile), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((plan.n_words_out, n), jnp.uint32),
        interpret=interpret,
    )(planes)
