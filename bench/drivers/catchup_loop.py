"""Replica catch-up: one read replica of the table tails its primary's
change-log stream and folds each batch into its standing index.

The replica comes up from the rows in host memory at the stream's
watermark (``StreamReplica.from_table``, one full rebuild, no genesis
frame).  Set-up makes every batch and its frame: a fire-and-forget
``StreamPrimary`` packs them on the host, as a primary's own CPU would.
Each batch is ``batch_share`` of the table, half deletes of live rows
never deleted before, half inserts that copy live rows with fresh
sequence numbers and record ids (``n``, ``n + 1``, ...), so the table
keeps its size.  A ``probe`` reserve of rows is never deleted.

The loop is closed: frame ``k`` is handed to the replica's transport once
the probe of batch ``k - 1`` is answered, then ``poll()`` (frame check and
decode, ``Replica.apply``: fold, insert rule, ``run_incremental``) and one
probe from the epoch it published: keys batch ``k`` inserted, keys it
deleted, reserved keys, and absent keys (last byte ``x``).  Set-up applies
and probes batch 0 to warm every shape; the window runs batches 1, 2, ...
until its time is up (the apply in flight finishes and counts) or the
batches run out.

``ready_s`` is the wall from the window's start to the end of the last
probe over the applies done.  The comparison holds the last state to a
NumPy fold of the log over the table (``bench/log_reference.py``), every
probe answer to the log and the table, and each probe's epoch to the
batch's last LSN."""

from __future__ import annotations

import io
import time
import traceback

import numpy as np

from bench import check, log_reference, reference, workload
from bench.systems import PIPELINE, ProgramSystem
from bench.table import pack_words

from repro.replication.stream import StreamReplica

if not hasattr(StreamReplica, "from_table"):
    raise ImportError("the catch-up cell brings its replica up with "
                      "StreamReplica.from_table, which this program lacks")

#: the fixed_record generator's seq(10) column starts at byte 16
SEQ_AT, SEQ_DIGITS = 16, 10
#: a wire frame's fixed header, before its npz body (repro.replication.wire)
FRAME_HEADER = 28


class ReplicaSystem(ProgramSystem):
    """The program's replica path: ``StreamReplica.from_table`` over the
    table in memory, frames handed to its ``QueueTransport``, ``poll()``,
    and the backend lookup on the epoch it pins.  ``pipe`` is the inner
    replica's pipeline, so a traced run spans its backend calls.  It
    subclasses ``ProgramSystem`` only because the harness spans the
    backend of a ``ProgramSystem``; the window never calls the
    ``rebuild`` it inherits."""

    def __init__(self, table, meta, pipeline_opts=None):  # noqa: D107
        from repro.core.keyformat import KeySet
        from repro.replication.transport import QueueTransport

        opts = dict(pipeline_opts or PIPELINE)
        backend, backend_opts = opts.pop("backend"), opts.pop("backend_opts", None)
        if opts:
            raise ValueError(f"a replica takes backend and backend_opts only, "
                             f"not {sorted(opts)}")
        self.transport = QueueTransport()
        self.stream = StreamReplica.from_table(
            self.transport,
            KeySet(words=table.words, lengths=table.lengths, rids=table.rids),
            table.n - 1, meta=meta, backend=backend, backend_opts=backend_opts)
        self.pipe = self.stream.replica.pipeline

    def deliver(self, frame: bytes) -> None:
        self.transport.publish(frame)

    def poll(self) -> dict:
        out = self.stream.poll()
        return {"timings": {**out["apply"]["timings"], "decode": out["decode"]}}

    def lookup(self, queries: np.ndarray):
        """``(found, rid, epoch, watermark)`` from one pinned snapshot."""
        import jax.numpy as jnp

        with self.stream.replica.snapshots.pin() as pin:
            found, rid = self.pipe.backend.lookup(pin.snapshot.tree,
                                                  jnp.asarray(queries))
            return (np.asarray(found), np.asarray(rid), pin.snapshot.epoch,
                    pin.snapshot.watermark)

    def state(self) -> dict:
        r = self.stream.replica.result
        return {
            "row_sorted": np.asarray(r.row_sorted),
            "comp_sorted": np.asarray(r.comp_sorted),
            "rid_sorted": np.asarray(r.rid_sorted),
            "tree_full": np.asarray(r.tree.sorted_full),
            "dbitmap": np.asarray(r.meta.dbitmap, np.uint32),
            "extract_bitmap": np.asarray(r.extract_bitmap, np.uint32),
        }

    def close(self) -> None:
        self.stream = self.transport = self.pipe = None


def make_system(table, meta, pipeline_opts):
    return ReplicaSystem(table, meta, pipeline_opts)


class ControlReplica:
    """The reference in the program's place, one step below the
    configuration: it applies each frame's inserts and drops its deletes,
    so an acknowledged delete is still found.  The comparison must fail
    it."""

    def __init__(self, table, meta, pipeline_opts=None):
        del pipeline_opts
        self.words, self.rids = table.words, table.rids
        self.dbitmap = np.asarray(meta.dbitmap, np.uint32)
        self.pending: list[bytes] = []
        self.lsn, self.epoch, self.sorted = table.n - 1, 0, None

    def deliver(self, frame: bytes) -> None:
        self.pending.append(frame)

    def poll(self) -> dict:
        for frame in self.pending:
            with np.load(io.BytesIO(frame[FRAME_HEADER:])) as z:
                ins = z["log_ops"] == 1
                self.words = np.concatenate([self.words, z["log_words"][ins]])
                self.rids = np.concatenate([self.rids, z["log_rids"][ins]])
                self.lsn = int(z["log_lsns"][-1])
        self.pending = []
        self.sorted = reference.SortedTable(self.words, self.rids)
        self.epoch += 1
        return {"timings": {}}

    def lookup(self, queries: np.ndarray):
        found, rid = self.sorted.lookup(queries)
        return found, rid, self.epoch, self.lsn

    def state(self) -> dict:
        w = self.words[self.sorted.order]
        return {
            "row_sorted": self.sorted.order.astype(np.uint32),
            "comp_sorted": reference.ref_extract(w, self.dbitmap),
            "rid_sorted": self.sorted.sorted_rids,
            "tree_full": w,
            "dbitmap": reference.ref_dbitmap(w),
            "extract_bitmap": self.dbitmap,
        }

    def close(self) -> None:
        self.sorted = None


# ---------------------------------------------------------------------------
# set-up: the batches, their frames and probes
# ---------------------------------------------------------------------------


def _with_seq(words: np.ndarray, seq: np.ndarray, key_bytes: int) -> np.ndarray:
    """``words`` with the seq column rewritten to ``seq``."""
    rows = reference.as_bytes(words).view(np.uint8).reshape(len(words), -1).copy()
    for i in range(SEQ_DIGITS):
        rows[:, SEQ_AT + i] = ord("0") + (seq // 10 ** (SEQ_DIGITS - 1 - i)) % 10
    return pack_words(rows[:, :key_bytes])


def _absent(words: np.ndarray, key_bytes: int) -> np.ndarray:
    """``words`` with the last key byte set to ``x``, which no key has."""
    out = words.copy()
    wi, shift = (key_bytes - 1) // 4, np.uint32(8 * (3 - (key_bytes - 1) % 4))
    out[:, wi] = (out[:, wi] & ~(np.uint32(0xFF) << shift)) | (np.uint32(ord("x")) << shift)
    return out


def setup(system, table, meta, mix, rng):
    from repro.replication import ChangeLog, QueueTransport, StreamPrimary

    n, key_bytes = table.n, int(table.lengths[0])
    if not np.array_equal(table.rids, np.arange(n, dtype=np.uint32)):
        raise ValueError("the catch-up traffic numbers rows by record id")
    m = int(round(n * float(mix["batch_share"]) / 2))
    probe = mix["probe"]
    n_reserved = min(4 * int(probe["live"]), n // 2)
    reserved = rng.choice(n, size=n_reserved, replace=False).astype(np.uint32)
    pool = np.setdiff1d(np.arange(n, dtype=np.uint32), reserved)
    inserted: list[np.ndarray] = []  # key words of rids n, n + 1, ...

    def words_of(rids):
        rids = np.asarray(rids, np.int64)
        out = np.empty((len(rids), table.words.shape[1]), np.uint32)
        base = rids < n
        out[base] = table.words[rids[base]]
        if not base.all():
            out[~base] = np.concatenate(inserted)[rids[~base] - n]
        return out

    def draw(rids, k):
        return rng.choice(rids, size=min(int(k), len(rids)), replace=False)

    primary_wire = QueueTransport()
    primary = StreamPrimary(primary_wire, keyset=None, n_words=table.words.shape[1],
                            start_lsn=n)
    batches, frames, lsn_last, probes, probe_rids = [], [], [], [], []
    next_rid = n
    for _ in range(1 + int(mix["batches"])):
        pos = rng.choice(len(pool), size=m, replace=False)
        dels = pool[pos]
        ins_rids = np.arange(next_rid, next_rid + m, dtype=np.uint32)
        ins_words = _with_seq(words_of(draw(pool, m)), ins_rids.astype(np.int64),
                              key_bytes)
        inserted.append(ins_words)
        next_rid += m
        pool = np.concatenate([np.delete(pool, pos), ins_rids])
        log = ChangeLog(table.words.shape[1], start_lsn=primary.next_lsn)
        log.append_deletes(dels)
        log.append_inserts(ins_words, ins_rids, lengths=np.full(m, key_bytes, np.int32))
        primary.publish(log)
        frames.append(primary_wire.read(primary_wire.end() - 1))
        lsn_last.append(log.next_lsn - 1)
        batches.append(log_reference.Batch(
            del_rids=dels, ins_words=ins_words,
            ins_lengths=np.full(m, key_bytes, np.int32), ins_rids=ins_rids))
        absent_from = draw(reserved, probe["absent"])
        rids = np.concatenate([draw(ins_rids, probe["inserted"]),
                               draw(dels, probe["deleted"]),
                               draw(reserved, probe["live"])]).astype(np.int64)
        probes.append(np.concatenate([words_of(rids),
                                      _absent(words_of(absent_from), key_bytes)]))
        probe_rids.append(np.concatenate([rids, np.full(len(absent_from), -1)]))
    del primary, primary_wire

    system.deliver(frames[0])  # warms every shape of an apply and a probe
    warm = system.poll()["timings"]
    if isinstance(system, ReplicaSystem) and "merge" not in warm:
        raise RuntimeError("the warm apply of batch 0 fell back to a full "
                           "rebuild, so the incremental programs would first "
                           "compile inside the window")
    system.lookup(probes[0])
    return {"table": table, "batches": batches, "frames": frames,
            "lsn_last": lsn_last, "probes": probes, "probe_rids": probe_rids}


# ---------------------------------------------------------------------------
# window, values, comparison
# ---------------------------------------------------------------------------


def window(system, sess, seconds):
    win = workload.Window()
    frames, probes = sess["frames"], sess["probes"]
    with workload.annotate("bench.window"):
        win.start = time.perf_counter()
        for k in range(1, len(frames)):  # set-up applied batch 0
            win.attempted += 1
            try:
                with workload.annotate("bench.apply"):
                    system.deliver(frames[k])
                    info = system.poll()
                    with workload.annotate("bench.probe"):
                        found, rid, epoch, mark = system.lookup(probes[k])
            except Exception:  # counts as failed; no later apply is timed
                win.failures.append(traceback.format_exc())
                break
            done = time.perf_counter()
            info.update(done=done, batch=k, found=found, rid=rid, epoch=epoch,
                        watermark=mark)
            win.rebuilds.append(info)
            if done - win.start >= seconds:
                break
        win.end = time.perf_counter()
    win.state = system.state() if win.rebuilds else None
    return win


def values(sess, win):
    if not win.rebuilds:
        return {}
    return {"ready_s": (win.rebuilds[-1]["done"] - win.start) / len(win.rebuilds)}


def compare(ref, sess, win):
    del ref  # the harness's reference holds the table before the log
    out = [("requests_failed", len(win.failures), 0)]
    if not win.rebuilds:
        return out
    table, batches = sess["table"], sess["batches"]
    words, _, rids = log_reference.fold(table.words, table.lengths, table.rids,
                                        batches[: win.rebuilds[-1]["batch"] + 1])
    folded = reference.SortedTable(words, rids)
    sorted_words = words[folded.order]
    exact = reference.ref_dbitmap(sorted_words)
    st = win.state
    missing = exact & ~st["extract_bitmap"]
    out += [
        ("rows_out_of_order", check.rows_differing(st["row_sorted"], folded.order), 0),
        ("compressed_keys_wrong", check.rows_differing(
            st["comp_sorted"], reference.ref_extract(sorted_words, st["extract_bitmap"])), 0),
        ("rids_wrong", check.rows_differing(st["rid_sorted"], folded.sorted_rids), 0),
        ("tree_keys_wrong", check.rows_differing(st["tree_full"], sorted_words), 0),
        ("dbitmap_words_wrong", check.rows_differing(st["dbitmap"], exact), 0),
        ("extraction_bits_missing", int(np.unpackbits(missing.view(np.uint8)).sum()), 0),
    ]
    wrong = stale = 0
    for r in win.rebuilds:
        k = r["batch"]
        found, rid = log_reference.expected(table.rids, batches[: k + 1],
                                            sess["probe_rids"][k])
        wrong += int(((r["found"] != found) | (r["rid"] != rid)).sum())
        stale += int(r["watermark"] != sess["lsn_last"][k])
    out += [("probe_answers_wrong", wrong, 0), ("probe_epochs_stale", stale, 0)]
    return out
