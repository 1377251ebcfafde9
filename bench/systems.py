"""What a window drives: the program under test, or the control.

Both offer ``rebuild(version)`` (rebuild the index from the table in host
memory, its record ids shifted as ``workload.rid_offset`` says for that
version, and publish it), ``lookup(queries) -> (found, rid, epoch)`` (one
pinned batch of point lookups, answered on the host) and ``state()`` (the last
rebuild's outputs as host arrays, for the comparison after the window).
Consecutive versions label the same keys with other record ids, so a
rebuild that hands back the previous index answers wrongly.
"""

from __future__ import annotations

import numpy as np

from bench import reference
from bench.table import Table
from bench.workload import rid_offset

#: the entry the cells time: the pallas backend with compiled kernels and
#: the pipeline's default chunking
PIPELINE = {"backend": "pallas", "backend_opts": {"interpret": False}}


class ProgramSystem:
    """``ReconstructionPipeline.run(..., publish_to=SnapshotCell)`` and the
    backend ``lookup`` on a pinned snapshot."""

    def __init__(self, table: Table, meta, pipeline_opts: dict | None = None):
        from repro.core.keyformat import KeySet
        from repro.core.pipeline import ReconstructionPipeline
        from repro.core.snapshot import SnapshotCell

        # both labelings are made here, so the window copies nothing
        self.keysets = [KeySet(words=table.words, lengths=table.lengths,
                               rids=table.rids + rid_offset(table, v))
                        for v in (0, 1)]
        self.meta = meta
        self.pipe = ReconstructionPipeline(**(pipeline_opts or PIPELINE))
        self.cell = SnapshotCell()
        self.last = None

    def rebuild(self, version: int) -> dict:
        self.last = None  # the cell keeps the published epoch alive
        self.last = self.pipe.run(self.keysets[version % 2], meta=self.meta,
                                  publish_to=self.cell)
        return {"epoch": self.cell.epoch, "timings": dict(self.last.timings)}

    def lookup(self, queries: np.ndarray):
        import jax.numpy as jnp

        with self.cell.pin() as pin:
            found, rid = self.pipe.backend.lookup(pin.snapshot.tree,
                                                  jnp.asarray(queries))
            return np.asarray(found), np.asarray(rid), pin.snapshot.epoch

    def state(self) -> dict:
        r = self.last
        return {
            "row_sorted": np.asarray(r.row_sorted),
            "comp_sorted": np.asarray(r.comp_sorted),
            "rid_sorted": np.asarray(r.rid_sorted),
            "tree_full": np.asarray(r.tree.sorted_full),
            "dbitmap": np.asarray(r.meta.dbitmap, np.uint32),
        }

    def close(self) -> None:
        self.last = self.cell = self.pipe = None


class ControlSystem:
    """The reference in the program's place, one step below what the
    configuration states: the rebuild sorts on the first compressed key
    word only (32 of the distinction bits, ties on the row), and a lookup
    returns its record id in 16 bits.  The comparison must fail it."""

    KEEP_BITS = 32

    def __init__(self, table: Table, meta, pipeline_opts: dict | None = None):
        del pipeline_opts
        self.table = table
        self.dbitmap = np.asarray(meta.dbitmap, np.uint32)
        self.epoch = -1
        self.sorted = None

    def rebuild(self, version: int) -> dict:
        w = self.table.words
        short = reference.ref_extract(w, self.dbitmap, keep_bits=self.KEEP_BITS)
        order = reference.ref_order(short)
        rids = self.table.rids + rid_offset(self.table, version)
        self.sorted = (order, reference.as_bytes(w)[order], rids[order],
                       rids[order] & np.uint32(0xFFFF))
        self.epoch += 1
        return {"epoch": self.epoch, "timings": {}}

    def lookup(self, queries: np.ndarray):
        _, sorted_bytes, _, rid16 = self.sorted
        qb = reference.as_bytes(queries)
        pos = np.minimum(np.searchsorted(sorted_bytes, qb), len(sorted_bytes) - 1)
        found = sorted_bytes[pos] == qb
        return found, np.where(found, rid16[pos], reference.NOT_FOUND_RID), self.epoch

    def state(self) -> dict:
        order, _, rid_sorted, _ = self.sorted
        w = self.table.words[order]
        return {
            "row_sorted": order.astype(np.uint32),
            "comp_sorted": reference.ref_extract(w, self.dbitmap),
            "rid_sorted": rid_sorted,
            "tree_full": w,
            "dbitmap": reference.ref_dbitmap(w),
        }

    def close(self) -> None:
        self.sorted = None
