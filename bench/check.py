"""Shared parts of the comparisons that decide ``correct``: counts of
answers that differ from the plain reference.  The configurations state
exact answers read from one published epoch, so every limit is 0."""

from __future__ import annotations

import numpy as np

from bench import reference
from bench.workload import rid_offset


def rows_differing(got: np.ndarray, want: np.ndarray) -> int:
    """Rows (first axis) in which ``got`` differs from ``want``; every row
    when the shapes differ."""
    got, want = np.asarray(got), np.asarray(want)
    if got.shape != want.shape:
        return max(len(got), len(want))
    return int((got != want).reshape(len(want), -1).any(axis=1).sum())


def rebuild_state(ref, table, meta_dbitmap: np.ndarray, state: dict,
                  version: int) -> list[tuple[str, int, int]]:
    """A rebuild's outputs (rows, compressed keys, rids, the tree's full
    keys, the refreshed D-bitmap) against the reference's."""
    order = ref.order
    sorted_words = table.words[order]
    return [
        ("rows_out_of_order", rows_differing(state["row_sorted"], order), 0),
        ("compressed_keys_wrong", rows_differing(
            state["comp_sorted"], reference.ref_extract(sorted_words, meta_dbitmap)), 0),
        ("rids_wrong", rows_differing(state["rid_sorted"],
                                      table.rids[order] + rid_offset(table, version)), 0),
        ("tree_keys_wrong", rows_differing(state["tree_full"], sorted_words), 0),
        ("dbitmap_words_wrong", rows_differing(
            state["dbitmap"], reference.ref_dbitmap(sorted_words)), 0),
    ]
