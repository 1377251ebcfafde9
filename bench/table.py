"""The base table a cell rebuilds its index from, as host arrays."""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass
class Table:
    """Keys of a table in row order.

    ``words`` holds each key's bytes, zero-padded, as big-endian uint32
    words (memcmp order is word-wise lexicographic order); ``lengths`` the
    key lengths in bytes; ``rids`` the record id each row maps to.
    ``sorted_words`` is the same keys in ascending order where the
    generator has that order for free, else ``None``.  ``data_id`` names
    what the contents depend on besides the configuration (the seed, or
    nothing), so per-table state can be cached under it.
    """

    words: np.ndarray
    lengths: np.ndarray
    rids: np.ndarray
    data_id: str
    sorted_words: np.ndarray | None = None

    @property
    def n(self) -> int:
        return int(self.words.shape[0])


def pack_words(rows: np.ndarray) -> np.ndarray:
    """(n, L) uint8 key bytes -> (n, ceil(L / 4)) big-endian uint32 words."""
    n, width = rows.shape
    n_words = max(1, -(-width // 4))
    buf = np.zeros((n, n_words * 4), np.uint8)
    buf[:, :width] = rows
    return buf.view(">u4").astype(np.uint32)
