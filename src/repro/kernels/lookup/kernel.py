"""Pallas TPU kernel: tiled partial-key probe for batched point lookups.

The paper's point lookup (§4.3, after Bohannon et al.) screens leaf
entries by their stored partial keys before paying a full-key dereference:
a true match requires the *query's* ``pk``-bit window at the entry's
distinction bit position to equal the entry's stored partial key.  This
kernel is that screen, vectorized over (query, entry) pairs:

* pairs stream through VMEM in ``tile``-lane blocks — the query's key as
  word planes (one (W, tile) block per grid step), the entry's window
  start position and stored partial key as (1, tile) planes alongside;
* the window extraction is the ``kernels/build`` straddle (branch-free
  per-plane compare+select word pick, double shift, top-``pk`` keep) —
  bit-identical to ``repro.core.btree._slice_bits`` by construction;
* the compare is one lane-wise uint32 equality, so the kernel emits the
  candidate mask directly and the caller derefs only screened lanes.

A full-key match always window-matches (the window is sliced from the
matching key itself), so masking a full-equality compare with this
screen is byte-identical to the unscreened compare — which is how the
pallas backend's ``lookup`` op stays bit-for-bit equal to the jnp oracle
while still exercising the partial-key economics.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_TILE = 512


def _probe_kernel(n_words: int, pk: int, w_ref, s_ref, p_ref, o_ref):
    """w_ref: (W, tile) query word planes; s_ref: (1, tile) int32 window
    start bits; p_ref: (1, tile) uint32 stored partial keys; o_ref:
    (1, tile) uint32 candidate mask (1 = window match).
    """
    start = jnp.clip(s_ref[0, :], 0, n_words * 32 - 1)
    wi = start // 32
    sh = (start % 32).astype(jnp.uint32)
    w0 = jnp.zeros(start.shape, jnp.uint32)
    w1 = jnp.zeros(start.shape, jnp.uint32)
    for w in range(n_words):
        plane = w_ref[w, :]
        w0 = jnp.where(wi == w, plane, w0)
        # wi + 1 == W selects nothing, leaving the zero fill — identical
        # to the oracle's where(wi + 1 < W, ..., 0)
        w1 = jnp.where(wi + 1 == w, plane, w1)
    hi = w0 << sh
    lo = jnp.where(sh == 0, jnp.uint32(0), w1 >> (jnp.uint32(32) - sh))
    window = (hi | lo) >> jnp.uint32(32 - pk)
    o_ref[0, :] = (window == p_ref[0, :]).astype(jnp.uint32)


@partial(jax.jit, static_argnames=("pk", "tile", "interpret"))
def probe_planes(
    word_planes: jnp.ndarray,
    starts: jnp.ndarray,
    entry_pk: jnp.ndarray,
    pk: int,
    *,
    interpret: bool,
    tile: int = DEFAULT_TILE,
) -> jnp.ndarray:
    """(W, n) query word planes + (n,) starts + (n,) stored partial keys
    -> (n,) uint32 candidate mask.  ``n`` must be a multiple of ``tile``."""
    w, n = word_planes.shape
    assert n % tile == 0, (word_planes.shape, tile)
    grid = (n // tile,)
    out = pl.pallas_call(
        partial(_probe_kernel, w, int(pk)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((w, tile), lambda i: (0, i)),
            pl.BlockSpec((1, tile), lambda i: (0, i)),
            pl.BlockSpec((1, tile), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, tile), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.uint32),
        interpret=interpret,
    )(
        word_planes,
        starts[None, :].astype(jnp.int32),
        entry_pk[None, :].astype(jnp.uint32),
    )
    return out[0]


def _probe_many_kernel(n_words: int, pk: int, w_ref, s_ref, p_ref, o_ref):
    """Tenant-major twin of ``_probe_kernel``.

    Refs carry a leading singleton tenant block — w_ref: (1, W, tile)
    query word planes, s_ref/p_ref/o_ref: (1, 1, tile) — and the math is
    identical lane-for-lane, so the fused multi-tenant probe stays
    bit-identical to the single-tenant kernel on each tenant's slice.
    """
    start = jnp.clip(s_ref[0, 0, :], 0, n_words * 32 - 1)
    wi = start // 32
    sh = (start % 32).astype(jnp.uint32)
    w0 = jnp.zeros(start.shape, jnp.uint32)
    w1 = jnp.zeros(start.shape, jnp.uint32)
    for w in range(n_words):
        plane = w_ref[0, w, :]
        w0 = jnp.where(wi == w, plane, w0)
        w1 = jnp.where(wi + 1 == w, plane, w1)
    hi = w0 << sh
    lo = jnp.where(sh == 0, jnp.uint32(0), w1 >> (jnp.uint32(32) - sh))
    window = (hi | lo) >> jnp.uint32(32 - pk)
    o_ref[0, 0, :] = (window == p_ref[0, 0, :]).astype(jnp.uint32)


@partial(jax.jit, static_argnames=("pk", "tile", "interpret"))
def probe_planes_many(
    word_planes: jnp.ndarray,
    starts: jnp.ndarray,
    entry_pk: jnp.ndarray,
    pk: int,
    *,
    interpret: bool,
    tile: int = DEFAULT_TILE,
) -> jnp.ndarray:
    """(T, W, n) stacked query word planes + (T, n) starts/partial keys
    -> (T, n) uint32 candidate mask.

    The grid gains a tenant-major axis — ``(T, n // tile)`` — so one
    ``pallas_call`` screens every tenant's (query, entry) pairs; each
    grid step streams one tenant's ``tile``-lane block through VMEM,
    which is the kernel-level realization of "one program, N tenants".
    ``n`` must be a multiple of ``tile``.
    """
    t, w, n = word_planes.shape
    assert n % tile == 0, (word_planes.shape, tile)
    grid = (t, n // tile)
    out = pl.pallas_call(
        partial(_probe_many_kernel, w, int(pk)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, w, tile), lambda t, i: (t, 0, i)),
            pl.BlockSpec((1, 1, tile), lambda t, i: (t, 0, i)),
            pl.BlockSpec((1, 1, tile), lambda t, i: (t, 0, i)),
        ],
        out_specs=pl.BlockSpec((1, 1, tile), lambda t, i: (t, 0, i)),
        out_shape=jax.ShapeDtypeStruct((t, 1, n), jnp.uint32),
        interpret=interpret,
    )(
        word_planes,
        starts[:, None, :].astype(jnp.int32),
        entry_pk[:, None, :].astype(jnp.uint32),
    )
    return out[:, 0, :]
