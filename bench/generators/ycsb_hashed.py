"""YCSB ``usertable`` keys with ``insertorder=hashed``: record ``i`` has the
key ``"user" + fnvhash64(i)`` and the record id ``i``; rows are in load
order.  The keys do not depend on the seed (YCSB's load phase is
deterministic); the requests drawn over them do."""

from __future__ import annotations

import numpy as np

from bench import ycsb
from bench.table import Table, pack_words


def make_table(cfg: dict, seed: int) -> Table:
    del seed
    n = int(cfg["recordcount"])
    rows, lengths = ycsb.key_names(np.arange(n, dtype=np.int64))
    return Table(
        words=pack_words(rows),
        lengths=lengths,
        rids=np.arange(n, dtype=np.uint32),
        data_id="load",
    )
