"""Pallas TPU kernel: in-VMEM bitonic block sort of multiword keys.

This is the VMEM analogue of the paper's ``basic_sort`` (Appendix A, step
3.1): the row-column sort keeps each base block inside the per-core L3
slice and quicksorts it.  Quicksort's data-dependent branches are hostile
to a vector unit, so the TPU-native block sort is a **bitonic network**:
O(log^2 T) compare-exchange substages, each a static permutation + select —
branch-free, fully lane-parallel, and entirely VMEM-resident.

Keys are (W, T) uint32 word planes plus a (1, T) payload plane (record id).
The comparator is the multiword lexicographic order (word 0 most
significant) — the same comparator the paper's sort uses, so compressing
keys shrinks ``W`` and with it the cost of *every* substage.

The partner exchange ``idx ^ j`` is two lane rotations per substage: lanes
with ``i & j == 0`` read their partner from ``roll(x, T - j)`` (lane
``i + j``), the others from ``roll(x, j)`` (lane ``i - j``).  Mosaic has no
lowering for a lane gather (``jnp.take``), but rotates lanes natively.
Every mask is a 2-D ``(1, T)`` vector built from integer ops: Mosaic does
not legalize a compare of two bool vectors, nor 1-D bool vectors.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK = 512


def _lex_cmp_planes(a, b, n_key_words: int):
    """a, b: (W, T) planes -> ((1, T) a<b, (1, T) a==b) lexicographic over
    the first ``n_key_words`` words."""
    less = a[0:1] < b[0:1]
    eq = a[0:1] == b[0:1]
    for w in range(1, n_key_words):
        aw, bw = a[w : w + 1], b[w : w + 1]
        less = less | (eq & (aw < bw))
        eq = eq & (aw == bw)
    return less, eq


def _bitonic_kernel(n_key_words: int, block: int, x_ref, o_ref):
    """x_ref/o_ref: (W+1, block) planes — key words + rid payload plane.

    Per substage, every lane decides *keep mine vs take partner's* from a
    lane-local comparison.  Lane ``i`` wants the smaller of the pair when
    bit ``sub`` and bit ``stage`` of ``i`` agree (the low lane of an
    ascending pair or the high lane of a descending one):
        keep = want_le ? (x <= p) : (x > p)   [ > as !(<) with eq split ]
    Ties keep both lanes' own entries (no payload duplication).
    """
    x = x_ref[...]
    # iota must be materialized in-kernel (captured constants are rejected)
    idx = jax.lax.broadcasted_iota(jnp.int32, (1, block), 1)
    log_t = int(np.log2(block))
    for stage in range(1, log_t + 1):
        for sub in range(stage - 1, -1, -1):
            j = 1 << sub
            is_lo = (idx & j) == 0
            want_le = (((idx >> sub) ^ (idx >> stage)) & 1) == 0
            px = jnp.where(
                is_lo, pltpu.roll(x, block - j, 1), pltpu.roll(x, j, 1)
            )
            lt, eq = _lex_cmp_planes(x, px, n_key_words)
            keep = (want_le & (lt | eq)) | (~want_le & ~lt)
            x = jnp.where(keep, x, px)
    o_ref[...] = x


@partial(jax.jit, static_argnames=("n_key_words", "block", "interpret"))
def bitonic_block_sort_planes(
    planes: jnp.ndarray,
    n_key_words: int,
    *,
    interpret: bool,
    block: int = DEFAULT_BLOCK,
) -> jnp.ndarray:
    """Sort each block of ``block`` lanes independently.

    planes: (W+1, n) uint32 — key word planes then one rid plane; ``n`` a
    multiple of ``block``.  Returns same shape, each block sorted by the
    first ``n_key_words`` planes (not stable: ties keep whichever rid the
    network leaves; the pad rid plane participates only as payload).
    ``interpret=True`` runs the kernel body under the Pallas interpreter
    (CPU tests); the TPU path passes ``False``.
    """
    wp, n = planes.shape
    assert n % block == 0 and (block & (block - 1)) == 0, (n, block)
    grid = (n // block,)
    return pl.pallas_call(
        partial(_bitonic_kernel, n_key_words, block),
        grid=grid,
        in_specs=[pl.BlockSpec((wp, block), lambda i: (0, i))],
        out_specs=pl.BlockSpec((wp, block), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((wp, n), jnp.uint32),
        interpret=interpret,
    )(planes)
