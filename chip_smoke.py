#!/usr/bin/env python3
"""Restart recovery on one TPU chip: the system's main path, end to end.

A main-memory database restarts and has to rebuild its index from the base
table and the persisted DS-metadata before it can answer reads (the
paper's recovery scenario).  This script plays that restart at the
published size of the INDBTAB stand-in — 16,384,000 keys of 35 bytes,
drawn from ``--seed`` — through the entry points a user calls:

1. ``table``:     generate the base table; the NumPy reference sorts it
                  (``np.lexsort``, row id as the last tie-break) and derives
                  the D-bitmap that the restart loads as persisted metadata.
2. ``kernels``:   the bitonic block-sort kernel against a per-block NumPy
                  sort (the pipeline re-sorts its output, so only this
                  check can see a wrong network).
3. ``recover``:   ``ReconstructionPipeline(backend="pallas",
                  backend_opts={"interpret": False})`` with default
                  chunking rebuilds the index and publishes it to a
                  ``SnapshotCell``; then the same rebuild once more, warm.
4. ``lookups``:   4,096 point lookups (half present, half absent) from the
                  pinned epoch through the backend ``lookup`` op.
5. ``jnp``:       the ``jnp`` backend rebuilds the same table; its sorted
                  keys, rows and tree must be byte-identical.
6. ``change_log``: a ~1% insert/delete log through ``run_incremental``,
                  published as the next epoch; lookups against the old
                  pinned epoch and the new one.

Every answer is checked against the NumPy reference.  Earlier lines print
set-up and first-run times (not benchmark numbers), compile seconds and
persistent-cache hits, and peak device memory.  The last line is one JSON
object, ``{"ok": true, "device": {...}}``, printed only when every check
passed.  Without a TPU the script exits non-zero before doing anything.

``--chips 4`` runs only the ``distributed`` backend's rebuild over a
four-device mesh and compares it byte for byte with the one-device ``jnp``
rebuild of the same table.

    python chip_smoke.py [--chips 1|4] [--seed 0] [--n-keys 16384000]
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

PUBLISHED_KEYS = 16_384_000  # INDBTAB, paper Table 2
N_QUERIES = 4096
CHANGE_FRAC = 0.01  # half deletes, half inserts
KERNEL_LANES = 1 << 20
NOT_FOUND_RID = np.uint32(0xFFFFFFFF)


class CheckFailed(AssertionError):
    """An answer of the system differs from the NumPy reference."""


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise CheckFailed(what)


def _log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# NumPy reference — independent of the code under test
# ---------------------------------------------------------------------------


def ref_order(words: np.ndarray) -> np.ndarray:
    """Row order of the lexicographic sort, row id as the final tie-break."""
    n, w = words.shape
    keys = (np.arange(n, dtype=np.uint32),) + tuple(
        words[:, i] for i in range(w - 1, -1, -1)
    )
    return np.lexsort(keys)


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


def ref_extract(words: np.ndarray, dbitmap: np.ndarray) -> np.ndarray:
    """Compressed keys: the D-bitmap's bits of each key, packed MSB-first."""
    pos = [
        wi * 32 + b
        for wi in range(dbitmap.shape[0])
        for b in range(32)
        if int(dbitmap[wi]) >> (31 - b) & 1
    ] or [0]
    n_out = (len(pos) + 31) // 32
    out = np.zeros((words.shape[0], n_out), np.uint32)
    for i, p in enumerate(pos):
        bit = (words[:, p // 32] >> np.uint32(31 - p % 32)) & np.uint32(1)
        out[:, i // 32] |= bit << np.uint32(31 - i % 32)
    return out


def _as_bytes(words: np.ndarray) -> np.ndarray:
    """Keys as fixed-width big-endian byte strings (memcmp order)."""
    w = np.ascontiguousarray(words.astype(">u4"))
    return w.view(f"S{4 * words.shape[1]}").ravel()


def ref_lookup(words, rids, order, queries):
    """Expected (found, rid) of each query: binary search over the sorted
    key bytes."""
    sorted_bytes = _as_bytes(words)[order]
    qb = _as_bytes(queries)
    pos = np.minimum(np.searchsorted(sorted_bytes, qb), len(sorted_bytes) - 1)
    found = sorted_bytes[pos] == qb
    rid = np.where(found, np.asarray(rids, np.uint32)[order][pos], NOT_FOUND_RID)
    return found, rid.astype(np.uint32)


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def make_table(n_keys: int, seed: int):
    """The INDBTAB stand-in at ``n_keys`` keys, from ``seed``."""
    from repro.configs.paper_index import DATASETS
    from repro.data.synthetic import dataset_keys

    return dataset_keys(dataclasses.replace(DATASETS["INDBTAB"], n_keys=n_keys),
                        seed=seed)


def persisted_meta(keyset, order):
    """The DS-metadata a restart loads: the table's exact D-bitmap."""
    from repro.core.metadata import DSMeta

    words = np.asarray(keyset.words, np.uint32)
    return DSMeta(
        dbitmap=ref_dbitmap(words[order]),
        varbitmap=np.bitwise_or.reduce(words ^ words[0], axis=0),
        refkey=words[0].copy(),
        n_words=keyset.n_words,
    )


def check_bitonic(comp: np.ndarray, interpret: bool) -> None:
    """The block-sort kernel against a per-block NumPy sort of the same
    (distinct) compressed keys."""
    import jax.numpy as jnp

    from repro.kernels.bitonic import ops as bitonic_ops
    from repro.kernels.bitonic.kernel import DEFAULT_BLOCK

    n = comp.shape[0] - comp.shape[0] % DEFAULT_BLOCK
    keys = comp[:n]
    rids = np.arange(n, dtype=np.uint32)
    got_k, got_r = bitonic_ops.block_sort(
        jnp.asarray(keys), jnp.asarray(rids), interpret=interpret
    )
    block = rids // DEFAULT_BLOCK
    want = np.lexsort(tuple(keys[:, i] for i in range(keys.shape[1] - 1, -1, -1))
                      + (block,))
    _check(np.array_equal(np.asarray(got_k), keys[want]), "bitonic block keys")
    _check(np.array_equal(np.asarray(got_r), rids[want]), "bitonic block rids")


def check_rebuild(res, keyset, order, meta) -> None:
    """Sorted compressed keys, rows, rids and full keys against the
    reference order; the refreshed D-bitmap against the reference one."""
    words = np.asarray(keyset.words, np.uint32)
    _check(np.array_equal(np.asarray(res.row_sorted), order), "sorted rows")
    _check(np.array_equal(np.asarray(res.comp_sorted),
                          ref_extract(words[order], meta.dbitmap)),
           "sorted compressed keys")
    _check(np.array_equal(np.asarray(res.rid_sorted),
                          np.asarray(keyset.rids)[order]), "sorted rids")
    _check(np.array_equal(np.asarray(res.tree.sorted_full), words[order]),
           "tree full keys")
    _check(np.array_equal(res.meta.dbitmap, ref_dbitmap(words[order])),
           "refreshed D-bitmap")


def make_queries(keyset, rng, n_queries: int) -> np.ndarray:
    """Half keys of the table, half absent keys (a table key whose last
    byte is not a digit)."""
    words = np.asarray(keyset.words, np.uint32)
    idx = rng.choice(keyset.n, size=n_queries, replace=False)
    q = words[idx].copy()
    absent = q[n_queries // 2 :]
    last = keyset.lengths[0] - 1  # byte index of the last key byte
    wi, shift = last // 4, 8 * (3 - last % 4)
    absent[:, wi] = (absent[:, wi] & ~np.uint32(0xFF << shift)) | np.uint32(
        ord("x") << shift
    )
    return q


def lookup(backend, snapshot, queries: np.ndarray):
    """Point lookups through the backend op against one pinned snapshot."""
    import jax.numpy as jnp

    found, rid = backend.lookup(snapshot.tree, jnp.asarray(queries))
    return np.asarray(found, bool), np.asarray(rid, np.uint32)


def check_lookups(backend, snapshot, keyset, order, queries, what: str) -> None:
    want_found, want_rid = ref_lookup(np.asarray(keyset.words, np.uint32),
                                      keyset.rids, order, queries)
    found, rid = lookup(backend, snapshot, queries)
    _check(np.array_equal(found, want_found), f"{what}: found")
    _check(np.array_equal(rid, want_rid), f"{what}: rid")


def make_change_log(keyset, rng, frac: float):
    """Deletes ``frac / 2`` of the rows and appends as many new records:
    copies of random rows with fresh sequence numbers and record ids."""
    from repro.core.keyformat import pack_rows

    n = keyset.n
    n_change = max(1, int(n * frac / 2))
    keep = np.ones(n, bool)
    keep[rng.choice(n, size=n_change, replace=False)] = False
    width = int(keyset.lengths[0])
    rows = _as_bytes(np.asarray(keyset.words, np.uint32)).view(np.uint8)
    rows = rows.reshape(n, -1)[rng.choice(n, size=n_change, replace=False), :width]
    seq = n + np.arange(n_change)
    for i in range(10):  # the 10-digit sequence column, bytes 16..25
        rows[:, 16 + i] = ord("0") + (seq // 10 ** (9 - i)) % 10
    delta = pack_rows(rows, rids=np.arange(n, n + n_change, dtype=np.uint32))
    return keep, delta


def apply_change_log(pipe, prev, keyset, keep, delta, cell):
    """Fold the log through ``run_incremental``; the D-bitmap keeps every
    bit of the previous extraction plus the folded table's own."""
    words = np.concatenate([np.asarray(keyset.words, np.uint32)[keep],
                            np.asarray(delta.words, np.uint32)])
    order = ref_order(words)
    meta = dataclasses.replace(
        prev.meta, dbitmap=prev.extract_bitmap | ref_dbitmap(words[order])
    )
    res, folded = pipe.run_incremental(prev, keyset, delta, keep_rows=keep,
                                       meta=meta, publish_to=cell)
    _check(np.array_equal(np.asarray(folded.words), words), "folded table")
    return res, folded, order, meta


def compare_results(a, b, what: str) -> None:
    """Byte-identity of two rebuilds: sorted keys, rows and every tree array."""
    import jax

    _check(np.array_equal(np.asarray(a.comp_sorted), np.asarray(b.comp_sorted)),
           f"{what}: sorted keys")
    _check(np.array_equal(np.asarray(a.row_sorted), np.asarray(b.row_sorted)),
           f"{what}: sorted rows")
    la, lb = jax.tree_util.tree_leaves(a.tree), jax.tree_util.tree_leaves(b.tree)
    _check(len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb)
    ), f"{what}: tree arrays")


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------


class CompileClock:
    """Seconds spent compiling (or loading compiled programs from the
    persistent cache), and how many programs came from that cache."""

    def __init__(self) -> None:
        import jax

        self.seconds = 0.0
        self.programs = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.programs += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def since(self, mark):
        return self.seconds - mark[0], self.programs - mark[1], self.cache_hits - mark[2]

    def mark(self):
        return self.seconds, self.programs, self.cache_hits


class Phases:
    """Runs named phases, printing each one's wall and, given a clock, its
    compile share."""

    def __init__(self, clock: CompileClock | None = None) -> None:
        self.clock = clock

    def __call__(self, name: str, fn, *args):
        mark = self.clock.mark() if self.clock else None
        t0 = time.perf_counter()
        out = fn(*args)
        msg = f"phase {name}: {time.perf_counter() - t0:.3f} s wall (first run, not a benchmark)"
        if self.clock:
            secs, progs, hits = self.clock.since(mark)
            msg += (f", compile {secs:.3f} s over {progs} programs, "
                    f"{hits} from the persistent cache")
        _log(msg)
        return out


def run_one_chip(n_keys: int, seed: int, phases: Phases, *,
                 interpret: bool = False, **pipeline_kwargs) -> None:
    """The whole one-chip scenario.  ``interpret`` and ``pipeline_kwargs``
    (chunking) exist for the CPU test at a tiny size; the script runs the
    defaults."""
    from repro.core.pipeline import ReconstructionPipeline
    from repro.core.snapshot import SnapshotCell

    rng = np.random.default_rng(seed)
    keyset = phases("table", make_table, n_keys, seed)
    order = phases("reference_order", ref_order, np.asarray(keyset.words))
    meta = phases("persisted_meta", persisted_meta, keyset, order)
    _log(f"table: {keyset.n} keys x {keyset.n_words} words, "
         f"{meta.n_dbits} distinction bits -> "
         f"{meta.plan().n_words_out} compressed words")

    comp = ref_extract(np.asarray(keyset.words[:KERNEL_LANES], np.uint32),
                       meta.dbitmap)
    phases("kernels", check_bitonic, comp, interpret)
    _log("check bitonic kernel vs NumPy block sort: pass")

    pipe = ReconstructionPipeline(backend="pallas",
                                  backend_opts={"interpret": interpret},
                                  **pipeline_kwargs)
    _log(f"pallas backend: interpret={pipe.backend.last_info['interpret']}, "
         f"merge={pipe.backend.last_info['merge']}")
    _check(pipe.backend.last_info["interpret"] is interpret, "interpret flag")
    cell = SnapshotCell()
    res = phases("recover", lambda: pipe.run(keyset, meta=meta, publish_to=cell))
    _log(f"recover stages (s): {json.dumps(res.timings, sort_keys=True)}")
    _log(f"recover stats: chunked={res.stats['chunked']} "
         f"merges={res.stats.get('cascade_merges')} "
         f"tree_height={res.stats['tree_height']}")
    phases("check_recover", check_rebuild, res, keyset, order, meta)
    _log("check rebuild vs NumPy reference: pass")
    again = phases("recover_warm", lambda: pipe.run(keyset, meta=meta))
    _log(f"recover_warm stages (s): {json.dumps(again.timings, sort_keys=True)}")
    compare_results(again, res, "warm rebuild")
    del again

    queries = make_queries(keyset, rng, N_QUERIES)
    with cell.pin() as snap:
        phases("lookups", check_lookups, pipe.backend, snap, keyset, order,
               queries, "lookups")
    _log(f"check {N_QUERIES} lookups at epoch {cell.epoch} vs NumPy: pass")

    jnp_pipe = ReconstructionPipeline(backend="jnp", **pipeline_kwargs)
    res_j = phases("jnp", lambda: jnp_pipe.run(keyset, meta=meta))
    compare_results(res_j, res, "jnp vs pallas")
    del res_j
    _log("check jnp backend byte-identical to pallas: pass")

    keep, delta = make_change_log(keyset, rng, CHANGE_FRAC)
    old = cell.acquire()
    res2, folded, order2, meta2 = phases(
        "change_log", apply_change_log, pipe, res, keyset, keep, delta, cell
    )
    _log(f"change_log: {int((~keep).sum())} deletes, {delta.n} inserts, "
         f"incremental={res2.stats.get('incremental')}, epoch {cell.epoch}")
    _log(f"change_log stages (s): {json.dumps(res2.timings, sort_keys=True)}")
    phases("check_change_log", check_rebuild, res2, folded, order2, meta2)
    _log("check incremental rebuild vs NumPy reference: pass")
    try:
        check_lookups(pipe.backend, old.snapshot, keyset, order, queries,
                      "old pinned epoch")
    finally:
        old.release()
    n_q = N_QUERIES // 4
    dropped = np.asarray(keyset.words)[~keep][:n_q]
    added = np.asarray(delta.words)[:n_q]
    queries2 = np.concatenate([dropped, added, queries[: n_q], queries[-n_q:]])
    with cell.pin() as snap:
        check_lookups(pipe.backend, snap, folded, order2, queries2, "new epoch")
    _log(f"check lookups on the old pinned epoch and on epoch {cell.epoch}: pass")


def run_four_chips(n_keys: int, seed: int, phases: Phases) -> None:
    """The ``distributed`` rebuild over every device against the one-device
    ``jnp`` rebuild and the NumPy reference."""
    from repro.core.pipeline import ReconstructionPipeline

    keyset = phases("table", make_table, n_keys, seed)
    order = phases("reference_order", ref_order, np.asarray(keyset.words))
    meta = phases("persisted_meta", persisted_meta, keyset, order)
    dist = ReconstructionPipeline(backend="distributed")
    _log(f"distributed backend: mesh of {dist.backend.n_devices} devices")
    res_d = phases("recover_distributed", lambda: dist.run(keyset, meta=meta))
    _log(f"recover_distributed stages (s): "
         f"{json.dumps(res_d.timings, sort_keys=True)}")
    res_j = phases("recover_jnp", lambda: ReconstructionPipeline(backend="jnp")
                   .run(keyset, meta=meta))
    compare_results(res_d, res_j, "distributed vs one-device jnp")
    _log("check distributed byte-identical to one-device jnp: pass")
    phases("check_recover", check_rebuild, res_d, keyset, order, meta)
    _log("check rebuild vs NumPy reference: pass")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--n-keys", type=int, default=PUBLISHED_KEYS)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform}); "
              "this script runs only on the chip", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} devices", file=sys.stderr)
        return 2

    from repro.core.plancache import enable_persistent_cache

    cache_dir = enable_persistent_cache(ROOT)
    dev = devices[0]
    _log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
         f"compile cache: {cache_dir}")
    if args.n_keys != PUBLISHED_KEYS:
        _log(f"cut: {args.n_keys} of the published {PUBLISHED_KEYS} keys")
    clock = CompileClock()
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            run_four_chips(args.n_keys, args.seed, Phases(clock))
        else:
            run_one_chip(args.n_keys, args.seed, Phases(clock))
    except CheckFailed as e:
        print(f"chip_smoke: check failed: {e}", file=sys.stderr)
        return 1
    stats = dev.memory_stats() or {}
    _log(f"compile total: {clock.seconds:.3f} s over {clock.programs} programs, "
         f"{clock.cache_hits} from the persistent cache")
    _log(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use')}")
    _log(f"total wall: {time.perf_counter() - t0:.3f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
