"""Async streaming replication tests (transport, protocol, catch-up).

The acceptance contract: a replica driven ONLY through the stream
transport — including one forced checkpoint catch-up — ends byte-identical
to a full ``ReconstructionPipeline.run`` over the folded keyset, on the
jnp and pallas backends.  Around it: transport semantics (positions,
atomic frames, retention), LSN watermark enforcement (out-of-order
rejected, duplicates idempotent, overlaps sliced), wire framing round
trips (including the shed-policy state regression), bounded-lag
backpressure, and the serve-layer standby (pager journal shipping +
engine follow mode).
"""

import numpy as np
import pytest

from repro.core import plancache
from repro.core.keyformat import KeySet
from repro.core.pipeline import ReconstructionPipeline
from repro.replication import (
    BatchFrame,
    ChangeLog,
    CheckpointFrame,
    DirectoryTransport,
    FrameTruncated,
    LsnGapError,
    QueueTransport,
    StreamPrimary,
    StreamReplica,
    decode_frame,
    encode_frame,
)


def _keyset(rng, n, w=3, mask=0x00FF0F0F, rid_base=0) -> KeySet:
    words = rng.integers(0, 2**32, size=(n, w), dtype=np.uint32) & np.uint32(mask)
    return KeySet(
        words=words,
        lengths=np.full(n, w * 4, np.int32),
        rids=np.arange(rid_base, rid_base + n, dtype=np.uint32),
    )


def _random_batch(rng, primary, n_ins=40, n_del=8, rid_base=100_000):
    """One LSN-contiguous batch re-drawing live keys (no new D-bits)."""
    ks = primary.replica.keyset
    log = ChangeLog(ks.n_words, start_lsn=primary.next_lsn)
    if n_ins:
        pick = rng.integers(0, ks.n, size=n_ins)
        log.append_inserts(
            np.asarray(ks.words)[pick],
            rid_base + rng.integers(0, 2**20, size=n_ins).astype(np.uint32),
        )
    if n_del:
        dead = rng.choice(np.asarray(ks.rids), size=min(n_del, ks.n), replace=False)
        log.append_deletes(dead)
    return log


def _assert_replica_state_identical(a, b):
    """Byte-identity of two replicas: keyset, metadata, standing result."""
    np.testing.assert_array_equal(np.asarray(a.keyset.words), np.asarray(b.keyset.words))
    np.testing.assert_array_equal(np.asarray(a.keyset.rids), np.asarray(b.keyset.rids))
    np.testing.assert_array_equal(a.meta.dbitmap, b.meta.dbitmap)
    np.testing.assert_array_equal(a.meta.varbitmap, b.meta.varbitmap)
    np.testing.assert_array_equal(a.meta.refkey, b.meta.refkey)
    np.testing.assert_array_equal(
        np.asarray(a.result.comp_sorted), np.asarray(b.result.comp_sorted)
    )
    np.testing.assert_array_equal(
        np.asarray(a.result.rid_sorted), np.asarray(b.result.rid_sorted)
    )
    assert a.applied_lsn == b.applied_lsn


def _assert_matches_full_run(rep, backend):
    """The stream-driven replica == a full pipeline run over its keyset."""
    full = ReconstructionPipeline(backend=backend).run(rep.keyset, meta=rep.meta)
    np.testing.assert_array_equal(
        np.asarray(rep.result.comp_sorted), np.asarray(full.comp_sorted)
    )
    np.testing.assert_array_equal(
        np.asarray(rep.result.rid_sorted), np.asarray(full.rid_sorted)
    )
    assert len(rep.result.tree.levels) == len(full.tree.levels)
    for la, lb in zip(rep.result.tree.levels, full.tree.levels):
        for k in la:
            np.testing.assert_array_equal(np.asarray(la[k]), np.asarray(lb[k]))


# ---------------------------------------------------------------------------
# transports
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kind", ["queue", "dir"])
def test_transport_semantics(tmp_path, kind):
    t = QueueTransport() if kind == "queue" else DirectoryTransport(tmp_path / "s")
    assert t.first_pos() == t.end() == 0 and t.read(0) is None
    for i in range(5):
        assert t.publish(f"frame{i}".encode()) == i
    assert t.end() == 5 and t.read(2) == b"frame2" and t.read(5) is None
    assert t.truncate_before(3) == 3
    assert t.first_pos() == 3 and len(t) == 2
    with pytest.raises(FrameTruncated):
        t.read(1)
    # positions never reused after truncation
    assert t.publish(b"six") == 5
    # truncating everything keeps the numbering
    t.truncate_before(6)
    assert t.first_pos() == t.end() == 6
    assert t.publish(b"seven") == 6


def test_directory_transport_ignores_partial_frames(tmp_path):
    t = DirectoryTransport(tmp_path / "s")
    t.publish(b"ok")
    # a torn write (no atomic rename yet) must be invisible to readers
    (tmp_path / "s" / ".tmp_frame_0000000001.bin").write_bytes(b"torn")
    assert t.end() == 1 and t.read(1) is None


# ---------------------------------------------------------------------------
# wire framing + shed-policy state round trip
# ---------------------------------------------------------------------------


def test_frame_roundtrip(rng):
    log = ChangeLog(2, start_lsn=7)
    log.append_inserts(rng.integers(0, 2**32, size=(5, 2), dtype=np.uint32),
                       np.arange(5, dtype=np.uint32))
    log.append_deletes([1, 2])
    f = decode_frame(encode_frame(BatchFrame(log=log, bucket=plancache.bucket(7))))
    assert isinstance(f, BatchFrame)
    assert f.lsn0 == 7 and f.lsn1 == 14 and f.bucket == plancache.bucket(7)
    a, b = log.arrays(), f.log.arrays()
    for k in a:
        np.testing.assert_array_equal(a[k], b[k])

    ck = CheckpointFrame(
        ckpt_dir="/some/dir", step=3, base_lsn=14,
        log_state=ChangeLog(2, start_lsn=14, shed_delete_frac=0.25,
                            deletes_since_shed=9),
    )
    g = decode_frame(encode_frame(ck))
    assert isinstance(g, CheckpointFrame)
    assert (g.ckpt_dir, g.step, g.base_lsn) == ("/some/dir", 3, 14)
    assert g.log_state.shed_delete_frac == 0.25
    assert g.log_state.deletes_since_shed == 9


def test_changelog_npz_preserves_shed_state(tmp_path):
    """Regression: the npz round trip used to drop the shed-policy state."""
    log = ChangeLog(2, shed_delete_frac=0.5, deletes_since_shed=3)
    log.append_inserts(np.asarray([[1, 2], [3, 4]], np.uint32), [0, 1])
    log.append_deletes([0, 1, 7])
    back = ChangeLog.load(log.save(tmp_path / "log.npz"))
    assert back.shed_delete_frac == 0.5
    assert back.deletes_since_shed == 3
    # None stays None (NaN encoding), counter survives a wire hop too
    log2 = ChangeLog.from_wire(ChangeLog(2, deletes_since_shed=4).to_wire())
    assert log2.shed_delete_frac is None and log2.deletes_since_shed == 4


def test_changelog_slice_and_concat(rng):
    log = ChangeLog(2, start_lsn=10)
    log.append_inserts(rng.integers(0, 2**32, size=(6, 2), dtype=np.uint32),
                       np.arange(6, dtype=np.uint32))
    log.append_deletes([0, 1])
    s = log.slice_lsn(12, 17)
    assert s.start_lsn == 12 and s.next_lsn == 17 and len(s) == 5
    assert (s.arrays()["lsns"] == np.arange(12, 17)).all()
    # stitching contiguous slices reproduces the original columns
    whole = ChangeLog.concat([log.slice_lsn(10, 13), log.slice_lsn(13, 18)])
    a, b = log.arrays(), whole.arrays()
    for k in a:
        np.testing.assert_array_equal(a[k], b[k])
    with pytest.raises(ValueError):
        ChangeLog.concat([log.slice_lsn(10, 12), log.slice_lsn(13, 18)])


# ---------------------------------------------------------------------------
# wire integrity: CRC header, typed decode failures, legacy fallback
# ---------------------------------------------------------------------------


def _sample_frame(rng) -> bytes:
    log = ChangeLog(2, start_lsn=3)
    log.append_inserts(rng.integers(0, 2**32, size=(4, 2), dtype=np.uint32),
                       np.arange(4, dtype=np.uint32))
    return encode_frame(BatchFrame(log=log, bucket=plancache.bucket(4)), seq=7)


def test_wire_crc32c_known_vector():
    """The checksum is real CRC32C (Castagnoli): the standard check value."""
    from repro.replication.wire import crc32c

    assert crc32c(b"123456789") == 0xE3069283
    assert crc32c(b"") == 0


def test_wire_header_carries_kind_and_seq(rng):
    from repro.replication import peek_header
    from repro.replication.wire import HEADER_SIZE

    raw = _sample_frame(rng)
    hdr = peek_header(raw)
    assert hdr.version == 1 and hdr.kind == 1 and hdr.seq == 7
    assert hdr.payload_len == len(raw) - HEADER_SIZE
    # legacy (headerless) payloads peek as None instead of exploding
    assert peek_header(raw[HEADER_SIZE:]) is None


def test_decode_rejects_bit_flips_as_frame_corrupt(rng):
    from repro.replication import FrameCorrupt

    raw = _sample_frame(rng)
    for flip_at in (5, len(raw) // 2, len(raw) - 1):
        damaged = bytearray(raw)
        damaged[flip_at] ^= 0x10
        with pytest.raises(FrameCorrupt):
            decode_frame(bytes(damaged))


def test_decode_rejects_truncation_and_padding(rng):
    from repro.replication import FrameCorrupt
    from repro.replication.wire import HEADER_SIZE

    raw = _sample_frame(rng)
    with pytest.raises(FrameCorrupt):
        decode_frame(raw[: HEADER_SIZE - 4])  # shorter than the header
    with pytest.raises(FrameCorrupt):
        decode_frame(raw[:-3])  # payload shorter than the header claims
    with pytest.raises(FrameCorrupt):
        decode_frame(raw + b"\x00\x00")  # padded past the claimed length


def test_decode_rejects_malformed_payloads_as_schema_errors(rng):
    import io

    from repro.replication import FrameSchemaError
    from repro.replication.wire import pack_frame, unpack_frame

    # unknown wire version (checked before the CRC would also fail it)
    bad = bytearray(_sample_frame(rng))
    bad[4] = 99  # the version byte sits right after the 4-byte magic
    with pytest.raises(FrameSchemaError):
        unpack_frame(bytes(bad))
    # unknown frame-kind tag (intact CRC, nonsense kind)
    with pytest.raises(FrameSchemaError):
        decode_frame(pack_frame(99, b"not-checked-yet"))
    # intact frame whose payload is not an npz archive
    with pytest.raises(FrameSchemaError):
        decode_frame(pack_frame(1, b"definitely not a zip"))
    # a valid npz missing the frame_kind discriminator
    buf = io.BytesIO()
    np.savez(buf, unrelated=np.arange(3))
    with pytest.raises(FrameSchemaError):
        decode_frame(pack_frame(1, buf.getvalue()))
    # header kind disagreeing with the payload's own kind string
    _, payload = unpack_frame(_sample_frame(rng))
    with pytest.raises(FrameSchemaError):
        decode_frame(pack_frame(2, payload))  # batch payload, shed tag
    # a batch frame with its log columns stripped
    buf = io.BytesIO()
    np.savez(buf, frame_kind=np.asarray("batch"))
    with pytest.raises(FrameSchemaError):
        decode_frame(pack_frame(1, buf.getvalue()))
    # legacy payload that is not an npz at all
    with pytest.raises(FrameSchemaError):
        decode_frame(b"ZZZZ this is no frame of any version")


def test_decode_legacy_v0_frames_still_works(rng):
    """Pre-header spools (PR-4 raw-npz frames) decode via the fallback."""
    from repro.replication.wire import HEADER_SIZE

    raw = _sample_frame(rng)
    legacy = raw[HEADER_SIZE:]  # exactly what v0 published: the bare npz
    f = decode_frame(legacy)
    assert isinstance(f, BatchFrame) and f.lsn0 == 3 and len(f.log) == 4


def test_primary_stamps_monotonic_wire_seq(rng):
    from repro.replication import peek_header

    t = QueueTransport()
    prim = StreamPrimary(t, _keyset(rng, 200))
    for _ in range(3):
        prim.publish(_random_batch(rng, prim, n_ins=5, n_del=0))
    seqs = [peek_header(t.read(i)).seq for i in range(t.end())]
    assert seqs == list(range(t.end()))  # dense, monotonic, no reuse
    assert prim.stats["wire_seq"] == t.end()


# ---------------------------------------------------------------------------
# the acceptance contract: stream-only replica == full run (jnp + pallas),
# including one forced checkpoint catch-up
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_stream_replica_byte_identical_with_catchup(tmp_path, backend):
    rng = np.random.default_rng(3)
    base = _keyset(rng, 1500)
    t = QueueTransport()
    prim = StreamPrimary(t, base, ckpt_dir=str(tmp_path / "ckpt"),
                         max_lag_batches=2)
    tail = StreamReplica(t, backend=backend)   # polls every batch
    lagger = StreamReplica(t, backend=backend)  # sleeps, then catches up
    tail.poll()
    for _ in range(9):
        prim.publish(_random_batch(rng, prim))
        tail.poll()
    # backpressure checkpointed + truncated repeatedly while lagger slept;
    # the active tail never needed the catch-up path (one-cycle retention)
    assert prim.stats["ckpt_step"] >= 2
    assert tail.stats["n_catchups"] == 0
    st = lagger.poll()
    assert st["catchup"] and lagger.stats["n_catchups"] == 1
    assert lagger.stats["n_truncation_jumps"] >= 1
    # the lagger then tailed the batches published after its bootstrap base
    assert lagger.stats["n_batches_applied"] > 0
    # the checkpoint chain has delta steps after the first forced one,
    # and the lagger's bootstrap restored through such a chain
    from repro.ckpt.checkpoint import step_manifest
    man = step_manifest(tmp_path / "ckpt", prim.stats["ckpt_step"])
    assert man["delta"] and man["base_step"] == prim.stats["ckpt_step"] - 1
    # never-lagged == caught-up == primary, and all == a full pipeline run
    _assert_replica_state_identical(tail.replica, prim.replica)
    _assert_replica_state_identical(lagger.replica, prim.replica)
    _assert_matches_full_run(tail.replica, backend)
    _assert_matches_full_run(lagger.replica, backend)


def test_bounded_lag_requires_checkpoint_config(rng):
    """max_lag_batches without a tracked index + ckpt_dir is rejected at
    construction — not mid-publish, where it would tear the stream."""
    from repro.replication import BackpressureError

    with pytest.raises(BackpressureError):
        StreamPrimary(QueueTransport(), n_words=2, max_lag_batches=3)
    with pytest.raises(BackpressureError):
        StreamPrimary(QueueTransport(), _keyset(rng, 50), max_lag_batches=3)


def test_stream_duplicate_and_out_of_order(rng):
    base = _keyset(rng, 600)
    t = QueueTransport()
    prim = StreamPrimary(t, base)
    rep = StreamReplica(t)
    rep.poll()
    log = _random_batch(rng, prim, n_ins=20, n_del=4)
    prim.publish(log)
    rep.poll()
    before = np.asarray(rep.replica.result.rid_sorted).copy()

    # duplicate delivery of the same frame is idempotent
    t.publish(t.read(1))
    st = rep.poll()
    assert st["duplicates"] == 1 and st["applied_batches"] == 0
    np.testing.assert_array_equal(before, np.asarray(rep.replica.result.rid_sorted))

    # a batch skipping past the watermark is rejected by the LSN check —
    # but a good batch drained in the same poll is applied first, and the
    # cursor parks on the offending frame (no frames are lost)
    good = _random_batch(rng, prim, n_ins=10, n_del=0)
    prim.publish(good)
    bad = ChangeLog(3, start_lsn=prim.next_lsn + 100)
    bad.append_inserts(np.asarray(base.words)[:1], [1])
    bad_pos = t.publish(encode_frame(BatchFrame(log=bad, bucket=plancache.bucket(1))))
    with pytest.raises(LsnGapError):
        rep.poll()
    assert rep.applied_lsn == good.next_lsn - 1  # good prefix was applied
    assert rep.pos == bad_pos                    # parked on the bad frame
    _assert_replica_state_identical(rep.replica, prim.replica)


def test_stream_overlapping_batch_sliced(rng):
    """Partial overlap (retransmission window) applies only the unseen
    suffix — byte-identical to exact-once delivery."""
    base = _keyset(rng, 500)
    t = QueueTransport()
    prim = StreamPrimary(t, base)
    rep = StreamReplica(t)
    rep.poll()
    l1 = _random_batch(rng, prim, n_ins=12, n_del=0)
    prim.publish(l1)
    rep.poll()
    l2 = ChangeLog(3, start_lsn=prim.next_lsn)
    l2.append_inserts(np.asarray(base.words)[:5],
                      np.arange(7000, 7005, dtype=np.uint32))
    # ships as one frame overlapping 4 already-applied entries
    both = ChangeLog.concat([l1, l2]).slice_lsn(l1.next_lsn - 4, l2.next_lsn)
    t.publish(encode_frame(BatchFrame(log=both, bucket=plancache.bucket(len(both)))))
    prim.replica.apply(l2)
    st = rep.poll()
    assert st["applied_batches"] == 1
    _assert_replica_state_identical(rep.replica, prim.replica)


def test_stream_coalesces_to_bucket(rng):
    """With coalescing on, small publishes buffer and ship as one batch
    whose size tags one plan-cache bucket."""
    base = _keyset(rng, 700)
    t = QueueTransport()
    prim = StreamPrimary(t, base, coalesce_min=64)
    rep = StreamReplica(t)
    rep.poll()
    genesis_frames = t.end()
    for _ in range(3):  # 3 x 16 entries: stays buffered
        prim.publish(_random_batch(rng, prim, n_ins=16, n_del=0))
    assert t.end() == genesis_frames and prim.stats["pending_entries"] == 48
    prim.publish(_random_batch(rng, prim, n_ins=16, n_del=0))  # hits 64
    assert t.end() == genesis_frames + 1
    frame = decode_frame(t.read(genesis_frames))
    assert len(frame.log) == 64 and frame.bucket == plancache.bucket(64)
    st = rep.poll()
    assert st["applied_batches"] == 1  # one rebuild for the coalesced span
    _assert_replica_state_identical(rep.replica, prim.replica)
    # explicit flush ships a short tail
    prim.publish(_random_batch(rng, prim, n_ins=5, n_del=0))
    assert prim.flush() == 5 and prim.flush() == 0
    rep.poll()
    _assert_replica_state_identical(rep.replica, prim.replica)


def test_watermark_noop_fast_path(rng):
    """An empty/cancelling change set advances the watermark without a
    rebuild and stays byte-identical (the pipeline no-op short circuit)."""
    from repro.replication import Replica

    base = _keyset(rng, 400)
    rep = Replica(base)
    standing = rep.result
    log = ChangeLog(3, start_lsn=0)
    log.append_inserts(np.asarray(base.words)[:1], [4242])
    log.append_deletes([4242])  # cancels the insert: net-empty batch
    st = rep.apply(log)
    assert st["noop"] and st["incremental"]
    assert rep.result.tree is standing.tree  # no rebuild happened
    assert rep.result.watermark == log.next_lsn - 1
    assert st["timings"]["build"] == 0.0
    _assert_matches_full_run(rep, "jnp")


# ---------------------------------------------------------------------------
# hypothesis: catch-up from a checkpoint chain == never-lagged replica
# ---------------------------------------------------------------------------


def _check_catchup(tmp_path, seed, n_batches, lag_from):
    rng = np.random.default_rng(seed)
    ckpt = tmp_path / f"ckpt_{seed}_{n_batches}_{lag_from}"
    t = QueueTransport()
    prim = StreamPrimary(t, _keyset(rng, 300), ckpt_dir=str(ckpt),
                         max_lag_batches=lag_from)
    tail = StreamReplica(t)
    lagger = StreamReplica(t)
    tail.poll()
    for _ in range(n_batches):
        prim.publish(_random_batch(
            rng, prim,
            n_ins=int(rng.integers(0, 30)),
            n_del=int(rng.integers(0, 10)),
        ))
        tail.poll()
    lagger.poll()
    if prim.stats["ckpt_step"] >= 2:
        # retention keeps one checkpoint cycle: a second checkpoint
        # truncated the lagger's tail, forcing the catch-up path
        assert lagger.stats["n_catchups"] >= 1
    _assert_replica_state_identical(tail.replica, prim.replica)
    _assert_replica_state_identical(lagger.replica, prim.replica)
    return lagger


def test_catchup_equals_never_lagged_hypothesis(tmp_path):
    pytest.importorskip("hypothesis")  # property tests need the dev extra
    from hypothesis import given, settings
    from hypothesis import strategies as st

    @settings(max_examples=8, deadline=None)
    @given(seed=st.integers(0, 10**6), n_batches=st.integers(3, 6),
           lag_from=st.integers(1, 2))
    def check(seed, n_batches, lag_from):
        _check_catchup(tmp_path, seed, n_batches, lag_from)

    check()


@pytest.mark.parametrize("seed,n_batches", [(149529, 3), (719470, 4), (97192, 3)])
def test_replay_from_origin_keeps_stale_dbits(tmp_path, seed, n_batches):
    """A replica that first polls after several batches, with every frame
    still retained, replays from the stream origin instead of a
    checkpoint.  It must bring up from the genesis batch and fold the
    rest, so the D-bits of deleted keys stay set as on the primary (each
    seed here deletes a key whose D-bit no surviving pair has)."""
    lagger = _check_catchup(tmp_path, seed, n_batches, lag_from=2)
    assert lagger.stats["n_catchups"] == 0


# ---------------------------------------------------------------------------
# shed adoption as a logged event (control frames)
# ---------------------------------------------------------------------------


def _stale_bit_base() -> KeySet:
    """Rows 0/1 differ only at bit 63; deleting both makes that bit stale."""
    words = np.zeros((6, 2), np.uint32)
    words[0] = (0, 0)
    words[1] = (0, 1)
    for i in range(2, 6):
        words[i] = (i << 8, 0)
    return KeySet(
        words=words, lengths=np.full(6, 8, np.int32),
        rids=np.arange(6, dtype=np.uint32),
    )


def test_shed_frame_keeps_replicas_identical_at_every_watermark():
    """The primary's shed lands in the stream as a control frame; a
    per-batch tailing replica and one draining the whole span in a single
    poll both adopt it at the shed watermark — byte-identical metadata and
    state at head, whatever the poll cadence (closes the ROADMAP item)."""
    from repro.replication import ShedFrame

    t = QueueTransport()
    prim = StreamPrimary(t, _stale_bit_base(), shed_delete_frac=0.1)
    tail = StreamReplica(t)   # polls after every publish
    span = StreamReplica(t)   # drains everything after genesis in one poll
    tail.poll()
    span.poll()

    shed_batch = ChangeLog(2, start_lsn=prim.next_lsn)
    shed_batch.append_deletes([0, 1])  # crosses the 10% threshold
    prim.publish(shed_batch)
    assert prim.stats["n_shed_frames"] == 1
    frame = decode_frame(t.read(t.end() - 1))
    assert isinstance(frame, ShedFrame)
    assert frame.lsn == shed_batch.next_lsn - 1
    # the frame round-trips through the wire encoding
    assert decode_frame(encode_frame(frame)) == frame

    st = tail.poll()
    assert st["shed_adopted"] == 1
    # the per-batch tail adopted at the watermark: stale bit 63 is gone
    # and the metadata equals the primary's exactly
    assert not (tail.replica.meta.dbitmap[1] & np.uint32(1))
    np.testing.assert_array_equal(tail.replica.meta.dbitmap,
                                  prim.replica.meta.dbitmap)

    post = ChangeLog(2, start_lsn=prim.next_lsn)
    post.append_inserts(np.asarray([[7 << 8, 0]], np.uint32), [100])
    prim.publish(post)
    tail.poll()
    st_span = span.poll()  # shed batch + shed frame + post batch, one poll
    assert st_span["shed_adopted"] == 1
    # the shed frame split the span: BOTH spans' apply stats are kept
    # ("applies"), and the post-shed one paid the full resort under the
    # narrow bitmap, exactly like the primary's
    assert len(st_span["applies"]) == 2
    assert st_span["applies"][0]["n_deleted"] == 2
    assert st_span["apply"] is st_span["applies"][-1]
    assert st_span["apply"]["fallback"] == "dbitmap_changed"
    for rep in (tail, span):
        _assert_replica_state_identical(rep.replica, prim.replica)
        _assert_matches_full_run(rep.replica, "jnp")


def test_shed_frame_stale_and_bootstrap_cases(tmp_path):
    """A bootstrapped replica's checkpoint already reflects the shed (the
    primary realigns before snapshotting); a stale duplicate shed frame at
    a watermark the replica is past is skipped, not re-adopted."""
    from repro.replication import ShedFrame

    t = QueueTransport()
    prim = StreamPrimary(t, _stale_bit_base(), shed_delete_frac=0.1,
                         ckpt_dir=str(tmp_path / "ckpt"))
    log = ChangeLog(2, start_lsn=prim.next_lsn)
    log.append_deletes([0, 1])
    prim.publish(log)  # sheds, publishes the control frame
    prim.checkpoint()  # realigned snapshot at the shed watermark
    late = StreamReplica(t, start_pos=t.end() - 1)  # only the ckpt frame
    st = late.poll()
    assert st["catchup"] and st["shed_adopted"] == 0
    _assert_replica_state_identical(late.replica, prim.replica)
    # a stale duplicate of the shed control frame (delivery fault) at a
    # watermark the replica has passed is skipped
    t.publish(encode_frame(ShedFrame(lsn=0)))
    st = late.poll()
    assert st["shed_adopted"] == 0 and late.stats["n_shed_adoptions"] == 0
    _assert_replica_state_identical(late.replica, prim.replica)


# ---------------------------------------------------------------------------
# serve layer: pager journal shipping + engine follow mode
# ---------------------------------------------------------------------------


def test_pager_ships_journal_and_standby_follows():
    from repro.serve.pager import PagedKVManager

    t = QueueTransport()
    pub = StreamPrimary(t, n_words=2)  # fire-and-forget publisher
    pm = PagedKVManager(n_pages=256, page_tokens=16)
    pm.attach_stream(pub)
    for s in range(6):
        pm.pages_for(s, 80)
    pm.rebuild_index()
    pm.free_seq(1)
    pm.pages_for(3, 160)
    pm.rebuild_index()

    standby = StreamReplica(t)
    standby.poll()
    for (s, p), phys in pm._table.items():
        found, rid = standby.search(np.asarray([s, p], np.uint32))
        assert found and rid == phys
    found, _ = standby.search(np.asarray([1, 0], np.uint32))
    assert not found  # freed sequence is gone on the standby too
    # a quiet rebuild (empty journal) ships nothing
    before = t.end()
    pm.rebuild_index()
    assert t.end() == before


def test_engine_follow_restart_replays_stream():
    import jax

    from repro.configs import ARCHS
    from repro.models.lm import LM
    from repro.serve.engine import ServeEngine

    cfg = ARCHS["llama3-8b"].reduced()
    m = LM(cfg, remat=False)
    params = m.init(jax.random.PRNGKey(0))
    t = QueueTransport()
    primary = ServeEngine(m, params, max_seq=64, batch_size=2, page_tokens=16)
    primary.pager.attach_stream(StreamPrimary(t, n_words=2))
    standby = ServeEngine(m, params, max_seq=64, batch_size=2, page_tokens=16)
    standby.follow(StreamReplica(t))

    prompts = np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 16))
    primary.generate(prompts, n_new=4)
    primary.restart()  # drains + ships the journal
    st = standby.restart()  # standby restart replays the stream
    assert st["followed_stream"] and st["lag_frames"] == 0
    assert st["applied_lsn"] == primary.pager._log.start_lsn - 1
    for (s, p), phys in primary.pager._table.items():
        found, rid = standby._follow.search(np.asarray([s, p], np.uint32))
        assert found and rid == phys
