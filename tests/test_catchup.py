"""Replica catch-up over the change-log stream, on INDBTAB-shaped keys.

A ``StreamReplica`` brought up from a table already in memory
(``StreamReplica.from_table``) must be byte-identical to one brought up
from the genesis frame over the same rows, and after every batch it
tails, its state must equal a full rebuild of the table folded through
that batch by the benchmark's NumPy reference (``bench/log_reference``).
The poll and apply paths carry spans and timings for the frame decode,
the fold and the insert rule.
"""

import glob
import sys
from pathlib import Path

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench import harness, log_reference  # noqa: E402
from bench.generators import fixed_record  # noqa: E402
from repro.core.keyformat import KeySet  # noqa: E402
from repro.core.metadata import meta_from_keys  # noqa: E402
from repro.core.pipeline import ReconstructionPipeline  # noqa: E402
from repro.replication import (  # noqa: E402
    ChangeLog,
    QueueTransport,
    StreamPrimary,
    StreamReplica,
)

N = 3000
KEY_BYTES = 35
BACKENDS = ["jnp", "pallas"]
DRIVER = harness.load_module(str(ROOT), "drivers", "catchup_loop")


def _opts(backend):
    return {"interpret": True} if backend == "pallas" else None


def _table(seed=7):
    t = fixed_record.make_table({"n_keys": N, "key_bytes": KEY_BYTES}, seed)
    return KeySet(words=t.words, lengths=t.lengths, rids=t.rids)


def _batch(rng, words_by_rid, live, next_rid, share=0.01, year=None,
           reinsert=False):
    """One batch of ``share`` of the table: half deletes of live rows, half
    inserts under fresh record ids.  The inserts copy live rows with fresh
    sequence numbers, as the benchmark's do; with ``year``, in that year;
    with ``reinsert``, they put the deleted keys back, which sets no new
    distinction bit, so the batch must merge."""
    m = max(1, int(round(len(live) * share / 2)))
    dels = rng.choice(live, size=m, replace=False)
    rids = np.arange(next_rid, next_rid + m, dtype=np.uint32)
    if reinsert:
        words = words_by_rid[dels]
    else:
        src = rng.choice(live, size=m, replace=False)
        words = DRIVER._with_seq(words_by_rid[src], rids.astype(np.int64), KEY_BYTES)
    if year is not None:
        words[:, 0] = np.uint32(int.from_bytes(str(year).encode(), "big"))
    return log_reference.Batch(del_rids=dels.astype(np.uint32), ins_words=words,
                               ins_lengths=np.full(m, KEY_BYTES, np.int32),
                               ins_rids=rids)


def _log(batch, start_lsn):
    log = ChangeLog(batch.ins_words.shape[1], start_lsn=start_lsn)
    log.append_deletes(batch.del_rids)
    log.append_inserts(batch.ins_words, batch.ins_rids, lengths=batch.ins_lengths)
    return log


def _tree_arrays(res):
    return [np.asarray(a) for level in res.tree.levels for a in level.values()]


def _assert_same_index(a, b):
    """Sorted rows, compressed keys, rids, tree arrays, D-bitmaps,
    watermark and epoch of two replicas' standing results."""
    ra, rb = a.replica.result, b.replica.result
    for x, y in [(ra.row_sorted, rb.row_sorted), (ra.comp_sorted, rb.comp_sorted),
                 (ra.rid_sorted, rb.rid_sorted), (ra.tree.sorted_full, rb.tree.sorted_full),
                 (ra.meta.dbitmap, rb.meta.dbitmap), (ra.extract_bitmap, rb.extract_bitmap),
                 (a.replica.meta.dbitmap, b.replica.meta.dbitmap),
                 (a.replica.meta.varbitmap, b.replica.meta.varbitmap)]:
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    ta, tb = _tree_arrays(ra), _tree_arrays(rb)
    assert len(ta) == len(tb)
    for x, y in zip(ta, tb):
        np.testing.assert_array_equal(x, y)
    assert ra.watermark == rb.watermark
    assert a.applied_lsn == b.applied_lsn
    assert a.replica.snapshots.epoch == b.replica.snapshots.epoch


def _assert_matches_reference(rep, table, batches, backend):
    """The replica == the reference fold of the log, then a full run under
    the replica's working metadata."""
    words, lengths, rids = log_reference.fold(table.words, table.lengths,
                                              table.rids, batches)
    np.testing.assert_array_equal(np.asarray(rep.replica.keyset.words), words)
    np.testing.assert_array_equal(np.asarray(rep.replica.keyset.rids), rids)
    full = ReconstructionPipeline(backend=backend, backend_opts=_opts(backend)).run(
        KeySet(words=words, lengths=lengths, rids=rids), meta=rep.replica.meta)
    res = rep.replica.result
    for x, y in [(res.row_sorted, full.row_sorted), (res.comp_sorted, full.comp_sorted),
                 (res.rid_sorted, full.rid_sorted), (res.meta.dbitmap, full.meta.dbitmap)]:
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    for x, y in zip(_tree_arrays(res), _tree_arrays(full)):
        np.testing.assert_array_equal(x, y)


class _Stream:
    """A table, a fire-and-forget primary past its rows, and a replica
    brought up from the table in memory."""

    def __init__(self, backend, seed=7):
        self.rng = np.random.default_rng(seed)
        self.table = _table(seed)
        self.words_by_rid = np.asarray(self.table.words)
        self.live = np.asarray(self.table.rids)
        self.batches = []
        self.wire = QueueTransport()
        self.primary = StreamPrimary(self.wire, n_words=self.table.n_words,
                                     start_lsn=N)
        self.replica = StreamReplica.from_table(
            self.wire, self.table, N - 1, meta=meta_from_keys(self.table.words),
            backend=backend, backend_opts=_opts(backend))

    def publish(self, **kw):
        b = _batch(self.rng, self.words_by_rid, self.live,
                   len(self.words_by_rid), **kw)
        self.words_by_rid = np.concatenate([self.words_by_rid, b.ins_words])
        self.live = np.concatenate([np.setdiff1d(self.live, b.del_rids), b.ins_rids])
        self.batches.append(b)
        self.primary.publish(_log(b, self.primary.next_lsn))
        return b


@pytest.mark.parametrize("backend", BACKENDS)
def test_table_bring_up_is_byte_identical_to_genesis(backend):
    table = _table()
    genesis_wire, memory_wire = QueueTransport(), QueueTransport()
    tracked = StreamPrimary(genesis_wire, table)  # publishes the genesis frame
    past_table = StreamPrimary(memory_wire, n_words=table.n_words, start_lsn=N)
    genesis = StreamReplica(genesis_wire, backend=backend, backend_opts=_opts(backend))
    genesis.poll()
    in_memory = StreamReplica.from_table(
        memory_wire, table, N - 1, meta=meta_from_keys(table.words),
        backend=backend, backend_opts=_opts(backend))
    _assert_same_index(in_memory, genesis)
    assert in_memory.stats["n_rebuilds"] == genesis.stats["n_rebuilds"] == 1

    # the same batch through both: poll and search_batch behave alike
    b = _batch(np.random.default_rng(1), np.asarray(table.words),
               np.asarray(table.rids), N)
    tracked.publish(_log(b, N))
    past_table.publish(_log(b, N))
    assert genesis.poll()["applied_batches"] == in_memory.poll()["applied_batches"] == 1
    _assert_same_index(in_memory, genesis)
    q = np.concatenate([b.ins_words, np.asarray(table.words)[b.del_rids]])
    for x, y in zip(in_memory.search_batch(q), genesis.search_batch(q)):
        np.testing.assert_array_equal(x, y)
    found, rid = in_memory.search_batch(q)
    assert found.tolist() == [True] * len(b.ins_rids) + [False] * len(b.del_rids)
    np.testing.assert_array_equal(rid[: len(b.ins_rids)], b.ins_rids)


@pytest.mark.parametrize("backend", BACKENDS)
def test_each_batch_equals_the_reference_fold_then_a_full_run(backend):
    s = _Stream(backend)
    for reinsert in (False, True, False):
        s.publish(reinsert=reinsert)
        st = s.replica.poll()
        assert st["applied_batches"] == 1
        assert s.replica.applied_lsn == s.primary.next_lsn - 1
        assert s.replica.replica.result.watermark == s.replica.applied_lsn
        _assert_matches_reference(s.replica, s.table, s.batches, backend)


@pytest.mark.parametrize("backend", BACKENDS)
def test_a_batch_that_adds_a_distinction_bit_falls_back_and_stays_exact(backend):
    s = _Stream(backend)
    s.publish()
    s.replica.poll()
    s.publish(year=2031)  # a year column no row has: a new distinction bit
    st = s.replica.poll()["apply"]
    assert st["fallback"] == "dbitmap_changed" and not st["incremental"]
    assert "merge" not in st["timings"]
    _assert_matches_reference(s.replica, s.table, s.batches, backend)
    s.publish(reinsert=True)  # the bits stay pinned: the next batch merges
    st = s.replica.poll()["apply"]
    assert st["incremental"] and "merge" in st["timings"]
    _assert_matches_reference(s.replica, s.table, s.batches, backend)


def test_poll_and_apply_report_decode_fold_and_insert_rule_seconds():
    s = _Stream("jnp")
    s.publish(reinsert=True)
    out = s.replica.poll()
    assert out["decode"] > 0.0
    timings = out["apply"]["timings"]
    assert timings["fold"] > 0.0 and timings["insert_rule"] > 0.0
    assert {"filter", "extract", "sort", "merge", "build"} <= set(timings)
    # a batch of deletes alone runs no insert rule
    log = ChangeLog(s.table.n_words, start_lsn=s.primary.next_lsn)
    log.append_deletes(s.live[:3])
    s.primary.publish(log)
    timings = s.replica.poll()["apply"]["timings"]
    assert timings["insert_rule"] == 0.0 and timings["fold"] > 0.0
    assert s.replica.poll()["decode"] == 0.0  # nothing to read


def _spans(log_dir):
    path = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))[-1]
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith("repro.")]


def test_new_spans_nest_under_the_poll(tmp_path):
    s = _Stream("jnp")
    s.publish()
    s.replica.poll()  # warm: the traced poll compiles nothing
    s.publish(reinsert=True)  # merges: one rebuild span
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        s.replica.poll()
    spans = _spans(tmp_path)
    names = [n for n, _, _ in spans]
    for name in ("repro.stream.poll", "repro.stream.decode", "repro.replica.apply",
                 "repro.replica.fold", "repro.replica.insert_rule", "repro.rebuild"):
        assert names.count(name) == 1, name

    def inside(inner, outer):
        (_, a, b), = [s for s in spans if s[0] == inner]
        (_, c, d), = [s for s in spans if s[0] == outer]
        return c <= a and b <= d

    assert inside("repro.stream.decode", "repro.stream.poll")
    assert inside("repro.replica.apply", "repro.stream.poll")
    for name in ("repro.replica.fold", "repro.replica.insert_rule", "repro.rebuild"):
        assert inside(name, "repro.replica.apply")


def test_table_bring_up_cursor():
    table = _table()
    wire = QueueTransport()
    primary = StreamPrimary(wire, n_words=table.n_words, start_lsn=N)
    rng = np.random.default_rng(2)
    first = _batch(rng, np.asarray(table.words), np.asarray(table.rids), N)
    primary.publish(_log(first, N))
    # the table is current through everything published so far
    assert StreamReplica.from_table(wire, table, N - 1 + len(_log(first, N))).pos == 1


def test_a_tracked_primary_starts_at_its_genesis():
    with pytest.raises(ValueError):
        StreamPrimary(QueueTransport(), _table(), start_lsn=5)
