"""Plain NumPy reference of a change log folded over a table: the rows a
replica must hold after each batch, and the answer each probe must get.
It imports nothing of the program.

A batch deletes rows by record id and appends its inserts, in log order,
after the surviving rows: the row order a replica's index numbers its
rows by.  The deletes of a batch name rows that were live before it (the
catch-up traffic makes them so), so the order of deletes and inserts
inside a batch does not matter.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from bench.reference import NOT_FOUND_RID


@dataclass
class Batch:
    """One change-log batch as columns: the record ids it deletes, then
    the rows it inserts (key words, key lengths, record ids)."""

    del_rids: np.ndarray
    ins_words: np.ndarray
    ins_lengths: np.ndarray
    ins_rids: np.ndarray


def fold(words: np.ndarray, lengths: np.ndarray, rids: np.ndarray,
         batches: list[Batch]) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """The table after ``batches``, in a replica's row order."""
    for b in batches:
        if np.isin(b.del_rids, b.ins_rids).any():
            raise ValueError("a batch deletes a row it inserts")
        keep = ~np.isin(rids, b.del_rids)
        words = np.concatenate([words[keep], b.ins_words])
        lengths = np.concatenate([lengths[keep], b.ins_lengths])
        rids = np.concatenate([rids[keep], b.ins_rids])
    return words, lengths, rids


def expected(base_rids: np.ndarray, batches: list[Batch], probe_rids: np.ndarray
             ) -> tuple[np.ndarray, np.ndarray]:
    """``(found, rid)`` of probe keys after ``batches``: each is the key of
    the row whose record id ``probe_rids`` gives, or of no row (-1).  A row
    is live when the base table or a batch inserted it and no batch
    deleted it; keys are distinct, so a live row's key finds its own
    record id and every other key misses."""
    probe_rids = np.asarray(probe_rids, np.int64)
    inserted = np.concatenate([np.asarray(base_rids, np.int64)]
                              + [np.asarray(b.ins_rids, np.int64) for b in batches])
    deleted = np.concatenate([np.zeros(0, np.int64)]
                             + [np.asarray(b.del_rids, np.int64) for b in batches])
    found = ((probe_rids >= 0) & np.isin(probe_rids, inserted)
             & ~np.isin(probe_rids, deleted))
    rid = np.where(found, probe_rids, np.int64(NOT_FOUND_RID)).astype(np.uint32)
    return found, rid
