"""The one traffic generator: what every traffic mix shares.

A mix is a data file, ``bench/traffic/<name>.json``.  Its ``driver`` names
the module ``bench/drivers/<driver>.py`` that owns the mix's set-up, its
window and the comparison of the answers it keeps; the other keys are that
driver's parameters.  A mix that an existing driver can run is added as a
data file alone; a mix with a new operation adds a driver file beside it.

This module holds what the drivers share: the query specs, the record-id
labelling of consecutive rebuilds, answer digests and the ``Window``
record.  A query spec: ``keys`` per request, ``distribution`` of the rows
it reads (``uniform_distinct``, or ``scrambled_zipfian`` as YCSB draws it
with the constant 0.99), ``absent_share`` (that share of the keys, at the
end, has its last byte set to ``x``), and ``sets``: how many distinct
requests are drawn from the seed in set-up; the window cycles through
them.
"""

from __future__ import annotations

import hashlib
import json
import os
from dataclasses import dataclass, field

import numpy as np

from bench import ycsb
from bench.table import Table


def load_mix(root: str, name: str) -> dict:
    with open(os.path.join(root, "bench", "traffic", f"{name}.json")) as f:
        mix = json.load(f)
    if not isinstance(mix.get("driver"), str):
        raise ValueError(f"traffic {name}: no driver named")
    return mix


def draw_rows(rng: np.random.Generator, n: int, spec: dict) -> np.ndarray:
    """``(sets, keys)`` table rows, one line per request."""
    sets, k = int(spec["sets"]), int(spec["keys"])
    dist = spec["distribution"]
    if dist == "uniform_distinct":
        return np.stack([rng.choice(n, size=k, replace=False) for _ in range(sets)])
    if dist == "scrambled_zipfian":
        if float(spec.get("zipf_constant", ycsb.ZIPF_CONSTANT)) != ycsb.ZIPF_CONSTANT:
            raise ValueError("scrambled_zipfian is YCSB's, with the constant 0.99")
        return ycsb.scrambled_zipfian(rng, n, sets * k).reshape(sets, k)
    raise ValueError(f"unknown distribution {dist!r}")


def make_sets(table: Table, rng: np.random.Generator, spec: dict) -> np.ndarray:
    """``(sets, keys, words)`` queries: rows of the table drawn by
    ``spec``, the last ``absent_share`` of each request altered in its
    last byte."""
    rows = draw_rows(rng, table.n, spec)
    q = table.words[rows]
    n_absent = int(round(rows.shape[1] * float(spec.get("absent_share", 0.0))))
    if n_absent:
        tail = rows[:, rows.shape[1] - n_absent:]
        last = table.lengths[tail].astype(np.int64) - 1
        wi, shift = last // 4, (8 * (3 - last % 4)).astype(np.uint32)
        s, r = np.indices(tail.shape)
        r = r + rows.shape[1] - n_absent
        mask = ~(np.uint32(0xFF) << shift)
        q[s, r, wi] = (q[s, r, wi] & mask) | (np.uint32(ord("x")) << shift)
    return q


def rid_offset(table: Table, version: int) -> np.uint32:
    """Record ids of rebuild ``version`` are the table's plus this: odd
    versions shift them by the table's size, so consecutive rebuilds map
    the same keys to other ids."""
    return np.uint32((version % 2) * table.n)


def expected(ref, table: Table, queries: np.ndarray, version: int):
    """The reference's ``(found, rid)`` for ``queries`` (any leading
    shape) against rebuild ``version``'s labelling."""
    lead = queries.shape[:-1]
    found, rid = ref.lookup(queries.reshape(-1, queries.shape[-1]))
    rid = np.where(found, rid + rid_offset(table, version), rid)
    return found.reshape(lead), rid.reshape(lead)


def digest(found: np.ndarray, rid: np.ndarray) -> bytes:
    """A fingerprint of one answer, so every request of the window can be
    compared after it without keeping every answer."""
    h = hashlib.blake2b(digest_size=16)
    h.update(np.ascontiguousarray(found, bool).tobytes())
    h.update(np.ascontiguousarray(rid, np.uint32).tobytes())
    return h.digest()


@dataclass
class Window:
    """What a window did, for the metrics and the comparison."""

    start: float = 0.0
    end: float = 0.0
    rebuilds: list = field(default_factory=list)  # dicts, in order
    requests: list = field(default_factory=list)  # (set, seconds, digest, epoch)
    failures: list = field(default_factory=list)  # formatted tracebacks
    attempted: int = 0
    state: dict | None = None  # what the comparison needs of the system


def annotate(name: str):
    """A host span in the profiler's trace (a no-op when none runs)."""
    import jax

    return jax.profiler.TraceAnnotation(name)
