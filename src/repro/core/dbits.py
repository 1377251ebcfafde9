"""Distinction bits (paper §3).

Keys are ``(n, W)`` ``uint32`` arrays, word 0 most significant, bit position
``p`` at word ``p // 32``, shift ``31 - (p % 32)`` (position 0 = global MSB,
matching the paper's numbering).

The central facts implemented here:

* Lemma 1:    D-bit(key_i, key_j) = min_{i<k<=j} D_k   (adjacent D-bits).
* Theorem 1:  the set of distinction bit positions over *all* pairs equals
              the set over *adjacent* pairs in sorted order, hence at most
              ``n`` positions for ``n+1`` keys.
* Theorem 2:  the bit slice at (a superset of) the distinction bit positions
              sorts the keys correctly.

``compute_dbitmap`` therefore only ever looks at adjacent keys of the sorted
input — O(n) work on top of the sort, exactly the paper's Remark 1.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "lex_less",
    "lex_compare_le",
    "sort_words",
    "sort_words_keyed",
    "rank_in_sorted_keyed",
    "merge_words_keyed",
    "adjacent_dbit_positions",
    "adjacent_dbitmap",
    "dbit_position_pairwise",
    "positions_to_bitmap",
    "bitmap_to_positions",
    "dbit_positions_nonempty",
    "bitmap_popcount",
    "compute_dbitmap",
    "compute_variant_bitmap",
    "NO_DBIT",
]

# Sentinel distinction-bit position for equal keys: one past the last bit.
NO_DBIT = np.int32(2**31 - 1)


# ---------------------------------------------------------------------------
# multiword lexicographic comparison
# ---------------------------------------------------------------------------

def lex_less(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Vectorized ``a < b`` for (..., W) uint32 keys, word 0 most significant."""
    lt = a < b
    eq = a == b
    # prefix of equal words before each position
    eq_prefix = jnp.cumprod(
        jnp.concatenate(
            [jnp.ones_like(eq[..., :1], dtype=jnp.int32), eq[..., :-1].astype(jnp.int32)],
            axis=-1,
        ),
        axis=-1,
    ).astype(bool)
    return jnp.any(lt & eq_prefix, axis=-1)


def lex_compare_le(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    eq = jnp.all(a == b, axis=-1)
    return lex_less(a, b) | eq


def sort_words_keyed(
    keys: jnp.ndarray, rows: jnp.ndarray, *payloads: jnp.ndarray
) -> tuple[jnp.ndarray, ...]:
    """Sort (n, W) keys with (n,) rows as the least-significant key word.

    The paper's sort key is literally the (compressed key, rid) pair; making
    the row a key word (not a stable-sort payload) is THE definition of the
    backend determinism contract — ascending (key, row) order regardless of
    input order — shared by every backend and the distributed merges.
    Returns (keys_sorted, rows_sorted, *payloads_sorted).
    """
    w = keys.shape[1]
    keyed = jnp.concatenate(
        [keys, jnp.asarray(rows, jnp.uint32)[:, None]], axis=1
    )
    out = sort_words(keyed, *payloads)
    return (out[0][:, :w], out[0][:, w]) + tuple(out[1:])


def sort_words(
    words: jnp.ndarray, *payloads: jnp.ndarray, num_key_words: int | None = None
) -> tuple[jnp.ndarray, ...]:
    """Lexicographic sort of (n, W) keys with payload arrays.

    Maps each of the first ``num_key_words`` word columns to a ``lax.sort``
    key operand — the multiword comparator of the paper, where the word count
    of the sort key directly sets the comparator cost.  Compression lowers
    ``num_key_words``; this is the mechanism by which the paper's word
    comparison ratio becomes a real speedup under XLA.
    """
    n, w = words.shape
    if num_key_words is None:
        num_key_words = w
    operands = tuple(words[:, i] for i in range(w)) + tuple(payloads)
    out = jax.lax.sort(operands, num_keys=num_key_words)
    sorted_words = jnp.stack(out[:w], axis=1)
    return (sorted_words,) + tuple(out[w:])


# ---------------------------------------------------------------------------
# merge of sorted (key, row) runs
# ---------------------------------------------------------------------------

def rank_in_sorted_keyed(
    keys_s: jnp.ndarray,
    rows_s: jnp.ndarray,
    keys_q: jnp.ndarray,
    rows_q: jnp.ndarray,
) -> jnp.ndarray:
    """Rank of each query pair in a sorted run: #{i : (key_s, row_s)_i < q}.

    ``(keys_s, rows_s)`` must be ascending in the (key, row) order of the
    backend determinism contract.  The query pairs need not be sorted.  This
    is the merge-path primitive: the output position of a run element in the
    two-run merge is its own index plus its rank in the *other* run.
    Vectorized binary search — log2(n_s) steps of whole-array lexicographic
    compares, no host loop.
    """
    ns = int(keys_s.shape[0])
    nq = int(keys_q.shape[0])
    if ns == 0 or nq == 0:
        return jnp.zeros((nq,), jnp.int32)
    lo = jnp.zeros((nq,), jnp.int32)
    hi = jnp.full((nq,), ns, jnp.int32)
    for _ in range(max(1, ns.bit_length())):
        mid = (lo + hi) // 2
        midc = jnp.minimum(mid, ns - 1)
        sk = keys_s[midc]
        sr = rows_s[midc]
        eq = jnp.all(sk == keys_q, axis=-1)
        lt = lex_less(sk, keys_q) | (eq & (sr < rows_q))
        lt = lt & (mid < ns)
        lo = jnp.where(lt, mid + 1, lo)
        hi = jnp.where(lt, hi, mid)
    return lo


def merge_words_keyed(
    keys_a: jnp.ndarray,
    rows_a: jnp.ndarray,
    keys_b: jnp.ndarray,
    rows_b: jnp.ndarray,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Merge two runs that are each ascending in (key, row) order.

    Byte-identical to ``sort_words_keyed`` over the concatenated pairs —
    rows must be distinct across both runs, so the (key, row) order is total
    and the merge is a permutation scatter: each element's output position
    is its own index plus its rank in the other run (O(n log n) comparisons
    for the ranks vs the full sort's network; O(n) data movement).  This is
    the reference semantics of the backend ``merge_sorted`` op, and the
    merge every single-device backend runs.
    """
    keys_a = jnp.asarray(keys_a, jnp.uint32)
    keys_b = jnp.asarray(keys_b, jnp.uint32)
    rows_a = jnp.asarray(rows_a, jnp.uint32)
    rows_b = jnp.asarray(rows_b, jnp.uint32)
    na, nb = int(keys_a.shape[0]), int(keys_b.shape[0])
    if na == 0:
        return keys_b, rows_b
    if nb == 0:
        return keys_a, rows_a
    # One rank pass, not two: rank the smaller run in the larger one, then
    # derive the larger run's positions from the complement.  The scatter
    # positions of the ranked run are exact; the other run fills the
    # remaining output slots in its own (ascending) order, so position p
    # holds element ``p - #{ranked elements before p}`` of the unranked
    # run.  That complement is one cumsum + one gather — O(n) — replacing
    # the second O(n log n) whole-array binary-search pass.  The resulting
    # permutation is identical to the two-pass construction, so the output
    # stays byte-identical to ``sort_words_keyed`` over the concatenation.
    if nb <= na:
        small_k, small_r, big_k, big_r = keys_b, rows_b, keys_a, rows_a
    else:
        small_k, small_r, big_k, big_r = keys_a, rows_a, keys_b, rows_b
    n_small, n_big = int(small_k.shape[0]), int(big_k.shape[0])
    n, w = na + nb, int(keys_a.shape[1])
    pos_s = (
        jnp.arange(n_small, dtype=jnp.int32)
        + rank_in_sorted_keyed(big_k, big_r, small_k, small_r)
    )
    occ = jnp.zeros((n,), jnp.int32).at[pos_s].set(1)
    # number of ranked (small-run) elements strictly before each position
    before = jnp.cumsum(occ) - occ
    big_idx = jnp.clip(
        jnp.arange(n, dtype=jnp.int32) - before, 0, n_big - 1
    )
    keys = jnp.where(
        (occ == 1)[:, None],
        jnp.zeros((n, w), jnp.uint32).at[pos_s].set(small_k),
        big_k[big_idx],
    )
    rows = jnp.where(
        occ == 1,
        jnp.zeros((n,), jnp.uint32).at[pos_s].set(small_r),
        big_r[big_idx],
    )
    return keys, rows


# ---------------------------------------------------------------------------
# distinction bit positions
# ---------------------------------------------------------------------------

def dbit_position_pairwise(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """D-bit(a, b) for (..., W) keys: MSB position where they differ.

    Returns NO_DBIT where the keys are equal.
    """
    x = a ^ b
    nz = x != 0
    any_nz = jnp.any(nz, axis=-1)
    first_word = jnp.argmax(nz, axis=-1)  # first differing word
    xw = jnp.take_along_axis(x, first_word[..., None], axis=-1)[..., 0]
    # clz of a uint32: number of leading zeros == bit offset of MSB set bit
    clz = jax.lax.clz(xw.astype(jnp.uint32)).astype(jnp.int32)
    pos = first_word.astype(jnp.int32) * 32 + clz
    return jnp.where(any_nz, pos, NO_DBIT)


def adjacent_dbit_positions(sorted_words: jnp.ndarray) -> jnp.ndarray:
    """D_i = D-bit(key_{i-1}, key_i) for i in 1..n-1 of sorted keys.

    Shape (n-1,).  Equal adjacent keys (duplicates) yield NO_DBIT which
    callers must mask before scattering into a bitmap.
    """
    return dbit_position_pairwise(sorted_words[:-1], sorted_words[1:])


def adjacent_dbitmap(sorted_words: jnp.ndarray, n_valid=None) -> jnp.ndarray:
    """OR of the adjacent D-bits of sorted keys as a (W,) uint32 bitmap.

    Pair ``i`` (rows ``i`` and ``i + 1``) contributes the most significant
    set bit of the XOR of its first differing word (MSB-first, as in
    :func:`positions_to_bitmap`); equal pairs contribute nothing.  Only
    pairs with ``i + 1 < n_valid`` (default: every row) count, so lanes at
    and past ``n_valid`` may hold anything.  The first differing word is a
    static select chain over the W words and the bitmap an OR-reduction:
    no position vector, no gather, no scatter.
    """
    w = jnp.asarray(sorted_words, jnp.uint32)
    n, n_words = int(w.shape[0]), int(w.shape[1])
    pair = jnp.arange(max(n - 1, 0), dtype=jnp.uint32)
    live = pair + 1 < (n if n_valid is None else n_valid)
    out = []
    for k in range(n_words):
        x = w[:-1, k] ^ w[1:, k]
        msb = jnp.where(x != 0, jnp.uint32(0x80000000) >> jax.lax.clz(x), 0)
        out.append(jax.lax.reduce(
            jnp.where(live, msb, jnp.uint32(0)), jnp.uint32(0),
            jax.lax.bitwise_or, dimensions=(0,),
        ))
        live = live & (x == 0)  # a pair's first differing word ends it
    return jnp.stack(out)


def positions_to_bitmap(positions: jnp.ndarray, n_words: int) -> jnp.ndarray:
    """Scatter bit positions into a (n_words,) uint32 bitmap (MSB-first)."""
    valid = positions != NO_DBIT
    pos = jnp.where(valid, positions, 0)
    word = pos // 32
    bit = jnp.where(valid, jnp.uint32(1) << (31 - (pos % 32)).astype(jnp.uint32), 0)
    zeros = jnp.zeros((n_words,), dtype=jnp.uint32)
    return _scatter_or(zeros, word, bit)


def _scatter_or(zeros: jnp.ndarray, word_idx: jnp.ndarray, bitmask: jnp.ndarray) -> jnp.ndarray:
    """OR-scatter bitmask values into words. Duplicate-safe."""
    n_words = zeros.shape[0]
    out = zeros
    # one plane per bit keeps the scatter duplicate-safe: a plane's scatter
    # writes the same value for every duplicate, so `.max` is an OR.
    for b in range(32):
        mask = jnp.uint32(1) << b
        plane = (bitmask & mask) != 0
        hits = jnp.zeros((n_words,), jnp.uint32).at[word_idx].max(plane.astype(jnp.uint32))
        out = out | (hits << b)
    return out


def bitmap_to_positions(bitmap: np.ndarray) -> np.ndarray:
    """Positions of set bits, ascending (host-side; bitmap is metadata)."""
    bm = np.asarray(bitmap, dtype=np.uint32)
    out = []
    for wi, w in enumerate(bm):
        w = int(w)
        for b in range(32):
            if w & (1 << (31 - b)):
                out.append(wi * 32 + b)
    return np.asarray(out, dtype=np.int32)


def dbit_positions_nonempty(bitmap: np.ndarray) -> np.ndarray:
    """``bitmap_to_positions`` with the degenerate-bitmap convention.

    An empty D-bitmap (all keys identical) yields the single position 0 so
    extraction plans, D-offset tables and tree builds all keep one-bit
    shapes — the ONE place this convention is defined.
    """
    pos = bitmap_to_positions(bitmap)
    if len(pos) == 0:
        pos = np.asarray([0], dtype=np.int32)
    return pos


def bitmap_popcount(bitmap: jnp.ndarray) -> jnp.ndarray:
    return jnp.sum(jax.lax.population_count(bitmap.astype(jnp.uint32)).astype(jnp.int32))


_dbitmap_from_sorted = jax.jit(adjacent_dbitmap)


def compute_dbitmap(words: jnp.ndarray, *, presorted: bool = False) -> jnp.ndarray:
    """D-bitmap of a key set: sort, then adjacent-pair distinction bits.

    By Theorem 1 this bitmap covers the distinction bit positions of *every*
    key pair.
    """
    w = jnp.asarray(words, dtype=jnp.uint32)
    if not presorted:
        (w,) = sort_words(w)
    return _dbitmap_from_sorted(w)


def compute_variant_bitmap(
    words: jnp.ndarray, reference: jnp.ndarray | None = None
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Variant bitmap + reference key (paper §4.2): OR of (key XOR reference).

    The reference key is an arbitrary member — we take row 0.
    """
    w = jnp.asarray(words, dtype=jnp.uint32)
    ref = w[0] if reference is None else jnp.asarray(reference, jnp.uint32)
    var = jax.lax.reduce(
        w ^ ref[None, :],
        jnp.uint32(0),
        jax.lax.bitwise_or,
        dimensions=(0,),
    )
    return var, ref
