"""Plain NumPy reference of the index semantics, independent of the code
under test: the order a rebuild must produce, the D-bitmap, the compressed
keys, and the answer of every point lookup.  It imports nothing of the
program."""

from __future__ import annotations

import numpy as np

NOT_FOUND_RID = np.uint32(0xFFFFFFFF)


def as_bytes(words: np.ndarray) -> np.ndarray:
    """Keys as fixed-width big-endian byte strings: NumPy compares them
    byte by byte, unsigned, which is memcmp order."""
    w = np.ascontiguousarray(np.asarray(words, np.uint32).astype(">u4"))
    return w.view(f"S{4 * w.shape[1]}").ravel()


def ref_order(words: np.ndarray) -> np.ndarray:
    """Row order of the lexicographic key sort, row position as the final
    tie-break (a stable sort of the key bytes)."""
    return np.argsort(as_bytes(words), kind="stable")


def ref_dbitmap(sorted_words: np.ndarray) -> np.ndarray:
    """D-bitmap of a sorted key set: the first differing bit of every
    adjacent pair (paper §3, Theorem 1)."""
    n, w = sorted_words.shape
    bitmap = np.zeros((w,), np.uint32)
    x = sorted_words[1:] ^ sorted_words[:-1]
    diff = x != 0
    has = diff.any(axis=1)
    word = diff.argmax(axis=1)[has]
    val = x[has, word]
    # bit offset of the most significant set bit, counted from the MSB
    msb = np.floor(np.log2(val.astype(np.float64))).astype(np.int64)
    for wi in range(w):
        bits = np.unique(msb[word == wi])
        bitmap[wi] = np.bitwise_or.reduce(np.uint32(1) << bits.astype(np.uint32),
                                          initial=np.uint32(0))
    return bitmap


def ref_extract(words: np.ndarray, dbitmap: np.ndarray, keep_bits: int | None = None
                ) -> np.ndarray:
    """Compressed keys: the D-bitmap's bits of each key, packed MSB-first.
    ``keep_bits`` keeps only the first that many distinction bits (the
    control's truncated key); the output width stays the full one."""
    pos = [
        wi * 32 + b
        for wi in range(dbitmap.shape[0])
        for b in range(32)
        if int(dbitmap[wi]) >> (31 - b) & 1
    ] or [0]
    n_out = (len(pos) + 31) // 32
    out = np.zeros((words.shape[0], n_out), np.uint32)
    for i, p in enumerate(pos[:keep_bits]):
        bit = (words[:, p // 32] >> np.uint32(31 - p % 32)) & np.uint32(1)
        out[:, i // 32] |= bit << np.uint32(31 - i % 32)
    return out


class SortedTable:
    """The reference's sorted view of a table, built once after the window
    and shared by every comparison of the run."""

    def __init__(self, words: np.ndarray, rids: np.ndarray) -> None:
        self.words = np.asarray(words, np.uint32)
        self.rids = np.asarray(rids, np.uint32)
        key_bytes = as_bytes(self.words)
        self.order = np.argsort(key_bytes, kind="stable")
        self.sorted_bytes = key_bytes[self.order]
        self.sorted_rids = self.rids[self.order]

    def lookup(self, queries: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """Expected ``(found, rid)`` of each query: binary search over the
        sorted key bytes; a miss answers ``NOT_FOUND_RID``."""
        qb = as_bytes(queries)
        pos = np.minimum(np.searchsorted(self.sorted_bytes, qb),
                         len(self.sorted_bytes) - 1)
        found = self.sorted_bytes[pos] == qb
        rid = np.where(found, self.sorted_rids[pos], NOT_FOUND_RID)
        return found, rid.astype(np.uint32)
