"""Synthetic datasets: the paper's generators + LM token streams.

``zipf_keys`` implements Zipf(s, n, m) of §6.3 *exactly*: 10M (scaled) keys
of n bytes; within each 8-byte word the first m bytes are one arbitrary
fixed ASCII value and the remaining 8-m bytes are lower-case ASCII drawn
from Zipf(s, 26).  Because the generator is fully specified, Table 4's
sort-key ratios are reproducible and validated in the benchmarks.

Table-2 stand-ins: the real INDBTAB/Human/Wikititle/ExURL/WikiURL/Part
datasets are not redistributable; generators here match their published
*shape* statistics (key length distribution, structure) so compression
behaviour is comparable, not identical — stated in EXPERIMENTS.md.
"""

from __future__ import annotations

import numpy as np

from repro.configs.paper_index import IndexDatasetConfig, ZipfConfig
from repro.core.keyformat import KeySet, keys_to_words, pack_rows

__all__ = ["zipf_keys", "dataset_keys", "lm_tokens"]


def _zipf_choice(rng: np.random.Generator, s: float, k: int, size) -> np.ndarray:
    """Draw from Zipf(s) truncated to {0..k-1} (paper's Zipf(s, 26))."""
    ranks = np.arange(1, k + 1, dtype=np.float64)
    p = ranks ** (-s)
    p /= p.sum()
    return rng.choice(k, size=size, p=p)


def zipf_keys(cfg: ZipfConfig, seed: int = 0, unique: bool = True) -> KeySet:
    """Zipf(s, n, m) keys of §6.3, packed."""
    rng = np.random.default_rng(seed)
    n_words8 = cfg.n_bytes // 8
    assert cfg.n_bytes % 8 == 0, "paper generator uses whole 8-byte words"
    fixed = ord("a")  # "an arbitrary fixed character"
    buf = np.empty((cfg.n_keys, cfg.n_bytes), dtype=np.uint8)
    for w in range(n_words8):
        lo = w * 8
        buf[:, lo : lo + cfg.m] = fixed
        z = _zipf_choice(rng, cfg.s, 26, (cfg.n_keys, 8 - cfg.m))
        buf[:, lo + cfg.m : lo + 8] = ord("a") + z
    if unique:
        # append a 4-byte sequence tail word-aligned? No — the paper keys may
        # collide; dedupe instead (sorting/compression assume distinct keys).
        buf = np.unique(buf, axis=0)
    keys = [bytes(r) for r in buf]
    return keys_to_words(keys)


def _url_like(rng, n, avg_len, max_len):
    """Hierarchical URLs: deep shared prefixes, distinction bits near the
    tail (matches the real ExURL/WikiURL dbit spread, paper Table 2)."""
    n_dom = max(n // 400, 8)
    doms = [f"www.site{int(i):04d}.org" for i in range(n_dom)]
    segs = ["wiki", "pages", "article", "item", "data", "ref", "cat", "id"]
    out = set()
    while len(out) < n:
        d = doms[int(rng.integers(0, n_dom))]
        depth = int(rng.integers(1, 4))
        path = "/".join(
            f"{segs[int(rng.integers(0, len(segs)))]}{int(rng.integers(0, 50))}"
            for _ in range(depth)
        )
        leaf = "".join(chr(97 + c) for c in rng.integers(0, 26, rng.integers(3, 9)))
        out.add(f"http://{d}/{path}/{leaf}{int(rng.integers(0, 10**4))}"
                .encode()[:max_len])
    return list(out)


def _genome_reads(rng, n, read_len):
    """EST-like reads: deep-coverage loci with point errors, so adjacent
    sorted reads share long prefixes and distinction bits spread across the
    whole read (the Human dataset's broad dbit profile, paper Table 2)."""
    genome = rng.integers(0, 4, size=max(n * 2, 100_000))
    acgt = np.frombuffer(b"ACGT", np.uint8)
    loci = rng.integers(0, len(genome) - read_len, size=max(n // 12, 4))
    out = set()
    while len(out) < n:
        off = int(loci[int(rng.integers(0, len(loci)))])
        read = genome[off : off + read_len].copy()
        # ~3 sequencing errors per read, uniform over positions
        for _ in range(int(rng.poisson(3))):
            read[int(rng.integers(0, read_len))] = int(rng.integers(0, 4))
        out.add(bytes(acgt[read]))
    return list(out)


def _title_like(rng, n, max_len):
    words = ["".join(chr(97 + c) for c in rng.integers(0, 26, rng.integers(3, 9)))
             for _ in range(2000)]
    out = []
    for _ in range(n):
        k = int(rng.integers(1, 4))
        t = "_".join(words[int(i)] for i in rng.integers(0, len(words), k))
        out.append(t.title().encode()[:max_len])
    return out


def _fixed_record(rng, n, width):
    """INDBTAB/Part-like: fixed-width multi-column business keys — a few
    low-cardinality columns + a sequence column (most bits invariant).

    Returns the (n, width) uint8 ASCII rows in ascending byte order:
    ``year(4) doc(8) item(4) seq(10)`` zero-padded decimal digits, then
    ``"0"`` fill.  Built one digit column at a time, so a table of tens of
    millions of rows takes seconds.  The seq column makes every row
    distinct, and fixed-width digits make byte order equal the numeric
    order of ``(doc, item, seq)``.
    """
    fields = (
        (np.full(n, 2024), 4),
        (rng.integers(0, 10000, n), 8),
        (rng.integers(0, 100, n), 4),
        (np.arange(n), 10),
    )
    if width < sum(d for _, d in fields):
        raise ValueError(f"fixed records need 26 bytes, got {width}")
    out = np.full((n, width), ord("0"), np.uint8)
    col = 0
    for v, n_digits in fields:
        for i in range(n_digits):
            out[:, col] = ord("0") + (v // 10 ** (n_digits - 1 - i)) % 10
            col += 1
    _, doc, item, seq = (v for v, _ in fields)
    return out[np.lexsort((seq, item, doc))]


def dataset_keys(cfg: IndexDatasetConfig, seed: int = 0) -> KeySet:
    rng = np.random.default_rng(seed)
    if cfg.kind == "fixed":
        rows = _fixed_record(rng, cfg.n_keys, cfg.key_bytes)
        return pack_rows(rows[rng.permutation(rows.shape[0])])
    if cfg.kind == "url":
        keys = _url_like(rng, cfg.n_keys, cfg.key_bytes, cfg.key_bytes * 2)
    elif cfg.kind == "title":
        keys = _title_like(rng, cfg.n_keys, cfg.key_bytes * 3)
    elif cfg.kind == "genome":
        keys = _genome_reads(rng, cfg.n_keys, cfg.key_bytes)
    elif cfg.kind == "zipf":
        n8 = ((cfg.key_bytes + 7) // 8) * 8
        return zipf_keys(
            ZipfConfig(cfg.zipf_s, n8, cfg.zipf_m, cfg.n_keys), seed=seed
        )
    else:
        raise ValueError(cfg.kind)
    keys = sorted(set(keys))
    rng.shuffle(keys)
    return keys_to_words(keys)


def lm_tokens(n_docs: int, doc_len: int, vocab: int, seed: int = 0) -> np.ndarray:
    """Zipf-distributed synthetic token stream, (n_docs, doc_len) int32."""
    rng = np.random.default_rng(seed)
    return _zipf_choice(rng, 1.1, vocab, (n_docs, doc_len)).astype(np.int32)
