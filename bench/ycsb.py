"""YCSB's key names and request distribution, vectorised.

Mirrors the YCSB core package (github.com/brianfrankcooper/YCSB, `core/src/
main/java/site/ycsb`): ``Utils.fnvhash64``, ``CoreWorkload.buildKeyName``
with ``insertorder=hashed`` and ``zeropadding=1``, and
``ScrambledZipfianGenerator`` over ``ZipfianGenerator`` with the constant
0.99.  Every function works on whole arrays; the tests hold each one to a
per-key Python loop that follows the Java line by line.
"""

from __future__ import annotations

import numpy as np

FNV_OFFSET_BASIS_64 = 0xCBF29CE484222325
FNV_PRIME_64 = 1099511628211

#: ``ScrambledZipfianGenerator``: the zipfian it scrambles runs over this many
#: items with a precomputed zeta, whatever the record count
ZIPF_ITEM_COUNT = 10_000_000_000
ZIPF_ZETAN = 26.46902820178302
ZIPF_CONSTANT = 0.99

#: "user" + at most 19 decimal digits of a non-negative long
KEY_BYTES = 24
_PREFIX = b"user"
_DIGITS = 19


def fnvhash64(values: np.ndarray) -> np.ndarray:
    """``Utils.fnvhash64``: FNV-1 over the 8 low-order bytes, then
    ``Math.abs`` of the signed result (so ``Long.MIN_VALUE`` stays
    negative, as in Java).  Returns int64."""
    v = np.asarray(values, np.int64).astype(np.uint64)
    h = np.full(v.shape, FNV_OFFSET_BASIS_64, np.uint64)
    prime = np.uint64(FNV_PRIME_64)
    for _ in range(8):
        h ^= v & np.uint64(0xFF)
        h *= prime  # wraps modulo 2**64, as Java's long multiply does
        v >>= np.uint64(8)
    return np.abs(h.view(np.int64))


def key_names(keynums: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """``buildKeyName`` with hashed insert order: ``"user"`` + the decimal
    ``fnvhash64(keynum)``.  Returns the zero-padded key bytes
    ``(n, KEY_BYTES)`` uint8 and the key lengths ``(n,)`` int32."""
    h = fnvhash64(keynums)
    if (h < 0).any():
        raise ValueError("a key number hashes to Long.MIN_VALUE")
    n = h.shape[0]
    # 19 ASCII digits, most significant first, one contiguous row per
    # digit, from three base-10**7 limbs so the arithmetic runs on uint32
    digits = np.empty((21, n), np.uint8)
    limbs = (h // 10**14, (h // 10**7) % 10**7, h % 10**7)
    for li, limb in enumerate(limbs):
        limb = limb.astype(np.uint32)
        for j in range(7):
            digits[7 * li + 6 - j] = limb % 10
            limb //= 10
    digits = digits[21 - _DIGITS :] + np.uint8(ord("0"))
    pow10 = np.array([10**j for j in range(1, _DIGITS)], np.int64)
    n_digits = 1 + np.searchsorted(pow10, h, side="right")
    start = len(_PREFIX)
    out = np.zeros((n, KEY_BYTES), np.uint8)
    out[:, :start] = np.frombuffer(_PREFIX, np.uint8)
    out[:, start : start + _DIGITS] = digits.T  # right for 19-digit hashes
    for nd in np.unique(n_digits[n_digits < _DIGITS]):  # left-align the rest
        rows = np.flatnonzero(n_digits == nd)
        out[rows, start : start + nd] = digits[_DIGITS - nd :, rows].T
        out[rows, start + nd :] = 0
    return out, (start + n_digits).astype(np.int32)


def zipfian_ranks(u: np.ndarray) -> np.ndarray:
    """``ZipfianGenerator(0, ZIPF_ITEM_COUNT, 0.99, ZIPF_ZETAN).nextValue()``
    for the uniform doubles ``u``.  Returns int64 ranks."""
    items = ZIPF_ITEM_COUNT + 1  # max - min + 1
    theta = ZIPF_CONSTANT
    zeta2theta = 1.0 + 0.5**theta
    alpha = 1.0 / (1.0 - theta)
    eta = (1.0 - (2.0 / items) ** (1.0 - theta)) / (1.0 - zeta2theta / ZIPF_ZETAN)
    u = np.asarray(u, np.float64)
    uz = u * ZIPF_ZETAN
    tail = (float(items) * np.power(eta * u - eta + 1.0, alpha)).astype(np.int64)
    return np.where(uz < 1.0, 0, np.where(uz < 1.0 + 0.5**theta, 1, tail))


def scrambled_zipfian(rng: np.random.Generator, record_count: int,
                      size: int) -> np.ndarray:
    """Key numbers as workload C's ``nextKeynum`` draws them:
    ``ScrambledZipfianGenerator(0, record_count)`` (``record_count + 1``
    items), redrawing any number past the last loaded record.  Returns
    ``size`` int64 key numbers in ``[0, record_count)``.  (Java's ``%``
    truncates, so a hash of ``Long.MIN_VALUE`` would give a negative
    number; it is redrawn here.)"""
    out = np.empty(0, np.int64)
    while out.shape[0] < size:
        ranks = zipfian_ranks(rng.random(size))
        keynums = np.fmod(fnvhash64(ranks), record_count + 1)
        keep = (keynums >= 0) & (keynums < record_count)
        out = np.concatenate([out, keynums[keep]])
    return out[:size]
