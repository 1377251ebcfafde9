"""INDBTAB-like fixed-width business keys (paper arXiv:2009.11543,
Table 2): ``year(4) doc(8) item(4) seq(10)`` zero-padded decimal columns,
then ``"0"`` fill to ``key_bytes``.  The doc and item columns are drawn
from the seed; the sequence column (the record's position in the draw)
makes every key distinct.  The rows come out in a seeded random order, as
a table in memory holds them.
"""

from __future__ import annotations

import numpy as np

from bench.table import Table

_COLUMNS = (("year", 4), ("doc", 8), ("item", 4), ("seq", 10))


def _sorted_words(rng: np.random.Generator, n: int, width: int) -> np.ndarray:
    """The keys in ascending order as (n, ceil(width / 4)) big-endian
    uint32 words.  Fixed-width digits make byte order the numeric order of
    ``(doc, item, seq)``, and ``seq`` is the draw position, so a stable
    sort on ``(doc, item)`` gives it."""
    if width < sum(d for _, d in _COLUMNS):
        raise ValueError(f"fixed records need 26 bytes, got {width}")
    doc = rng.integers(0, 10000, n)
    item = rng.integers(0, 100, n)
    order = np.argsort(doc * 100 + item, kind="stable")
    values = {"year": np.full(n, 2024), "doc": doc[order], "item": item[order],
              "seq": order}
    n_words = -(-width // 4)
    # one contiguous row per key byte, so every write below is a plain pass
    cols = np.full((n_words * 4, n), ord("0"), np.uint8)
    cols[width:] = 0
    at = 0
    for name, n_digits in _COLUMNS:
        v = values[name].astype(np.uint32)
        for i in range(n_digits - 1, -1, -1):
            cols[at + i] = ord("0") + v % 10  # fits a byte
            v //= 10
        at += n_digits
    c = [cols[i::4].astype(np.uint32) for i in range(4)]
    words = c[0] << 24 | c[1] << 16 | c[2] << 8 | c[3]
    return np.ascontiguousarray(words.T)


def make_table(cfg: dict, seed: int) -> Table:
    rng = np.random.default_rng(seed)
    n, width = int(cfg["n_keys"]), int(cfg["key_bytes"])
    sorted_words = _sorted_words(rng, n, width)
    words = sorted_words[rng.permutation(n)]
    return Table(
        words=words,
        lengths=np.full(n, width, np.int32),
        rids=np.arange(n, dtype=np.uint32),
        data_id=f"seed{seed}",
        sorted_words=sorted_words,
    )
