"""Replication layer + incremental reconstruction tests (no dev extras).

The merge contract (repro.backends.base): ``merge_sorted`` over two
ascending (key, row) runs must be byte-identical to ``sort`` over their
concatenation, on every backend.  ``run_incremental`` layers the same
guarantee end to end: its sorted compressed keys, rid permutation and tree
levels must match a full ``run`` over the folded keyset — including on
duplicate-heavy keysets, deletes-only / empty-delta edge cases, and with
the full-path fallback when the D-bitmap grew.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.backends import get_backend
from repro.core.dbits import merge_words_keyed, sort_words_keyed
from repro.core.keyformat import KeySet
from repro.core.metadata import meta_from_keys
from repro.core.pipeline import ReconstructionPipeline, fold_keyset
from repro.replication import ChangeLog, Replica

BACKENDS = ("jnp", "pallas", "distributed")


def _keyset(rng, n, w=3, mask=0x00FF0F0F, rid_base=0) -> KeySet:
    words = rng.integers(0, 2**32, size=(n, w), dtype=np.uint32) & np.uint32(mask)
    return KeySet(
        words=words,
        lengths=np.full(n, w * 4, np.int32),
        rids=np.arange(rid_base, rid_base + n, dtype=np.uint32),
    )


def _sorted_run(rng, n, w, mask, rows):
    keys = rng.integers(0, 2**32, size=(n, w), dtype=np.uint32) & np.uint32(mask)
    if n == 0:
        return jnp.asarray(keys), jnp.asarray(rows, jnp.uint32)
    return sort_words_keyed(jnp.asarray(keys), jnp.asarray(rows, jnp.uint32))


def _assert_result_identical(a, b):
    np.testing.assert_array_equal(np.asarray(a.comp_sorted), np.asarray(b.comp_sorted))
    np.testing.assert_array_equal(np.asarray(a.rid_sorted), np.asarray(b.rid_sorted))
    np.testing.assert_array_equal(np.asarray(a.row_sorted), np.asarray(b.row_sorted))
    np.testing.assert_array_equal(a.meta.dbitmap, b.meta.dbitmap)
    assert len(a.tree.levels) == len(b.tree.levels)
    for la, lb in zip(a.tree.levels, b.tree.levels):
        for k in la:
            np.testing.assert_array_equal(np.asarray(la[k]), np.asarray(lb[k]))
    for k in a.tree.leaf:
        np.testing.assert_array_equal(
            np.asarray(a.tree.leaf[k]), np.asarray(b.tree.leaf[k])
        )


# ---------------------------------------------------------------------------
# merge_sorted backend contract
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("na,nb", [(3000, 150), (500, 500), (100, 0), (0, 100)])
def test_merge_sorted_matches_sort_every_backend(rng, na, nb):
    """Duplicate-heavy runs with interleaved row ids: the merge must equal
    the full keyed sort of the concatenation, byte for byte."""
    w, mask = 3, 0x000F0F0F  # heavy duplicates
    rows = np.arange(na + nb, dtype=np.uint32)
    rng.shuffle(rows)
    ka, ra = _sorted_run(rng, na, w, mask, rows[:na])
    kb, rb = _sorted_run(rng, nb, w, mask, rows[na:])
    all_k = jnp.concatenate([ka, kb], axis=0)
    all_r = jnp.concatenate([ra, rb])
    ref_k, ref_r = sort_words_keyed(all_k, all_r)
    for name in BACKENDS:
        mk, mr = get_backend(name).merge_sorted(ka, ra, kb, rb)
        np.testing.assert_array_equal(np.asarray(mk), np.asarray(ref_k), err_msg=name)
        np.testing.assert_array_equal(np.asarray(mr), np.asarray(ref_r), err_msg=name)


def test_merge_sorted_pallas_matches_jnp(rng):
    """The pallas merge is the XLA merge: bucket-padded ladder-form calls
    (``n_valid`` + ``keep_padded``) give the jnp backend's bytes."""
    w, mask = 2, 0x3F
    ka, ra = _sorted_run(rng, 200, w, mask, np.arange(200, dtype=np.uint32))
    kb, rb = _sorted_run(rng, 77, w, mask, np.arange(200, 277, dtype=np.uint32))
    pallas = get_backend("pallas", interpret=True)
    assert pallas.last_info["merge"] == "xla"
    outs = [
        be.merge_sorted(
            jnp.pad(ka, ((0, 56), (0, 0))), jnp.pad(ra, (0, 56)),
            jnp.pad(kb, ((0, 51), (0, 0))), jnp.pad(rb, (0, 51)),
            n_valid_a=200, n_valid_b=77, keep_padded=True,
        )
        for be in (pallas, get_backend("jnp"))
    ]
    for got, want in zip(outs[0], outs[1]):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_merge_words_keyed_is_permutation(rng):
    """The rank scatter must be collision-free for distinct rows."""
    ka, ra = _sorted_run(rng, 512, 2, 0x7, np.arange(512, dtype=np.uint32))
    kb, rb = _sorted_run(rng, 256, 2, 0x7, np.arange(512, 768, dtype=np.uint32))
    mk, mr = merge_words_keyed(ka, ra, kb, rb)
    assert sorted(np.asarray(mr).tolist()) == list(range(768))


# ---------------------------------------------------------------------------
# run_incremental == full run on the folded keyset
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", BACKENDS)
def test_run_incremental_byte_identical(rng, backend):
    n, nd, w = 4000, 200, 3
    all_words = rng.integers(0, 2**32, size=(n + nd, w), dtype=np.uint32) & np.uint32(
        0x00FF0F0F
    )
    meta = meta_from_keys(all_words)  # union metadata: no bit growth later
    base = KeySet(
        words=all_words[:n], lengths=np.full(n, 12, np.int32),
        rids=np.arange(n, dtype=np.uint32),
    )
    delta = KeySet(
        words=all_words[n:], lengths=np.full(nd, 12, np.int32),
        rids=np.arange(50_000, 50_000 + nd, dtype=np.uint32),
    )
    keep = rng.random(n) > 0.05
    pipe = ReconstructionPipeline(backend=backend)
    prev = pipe.run(base, meta=meta)
    inc, folded = pipe.run_incremental(prev, base, delta, keep_rows=keep, meta=meta)
    assert inc.stats["incremental"] is True
    assert inc.stats["n_delta"] == nd
    assert inc.stats["n_deleted"] == int(n - keep.sum())
    full = pipe.run(folded, meta=meta)
    _assert_result_identical(inc, full)


def test_run_incremental_empty_delta_and_deletes_only(rng):
    n = 1500
    base = _keyset(rng, n)
    meta = meta_from_keys(base.words)
    pipe = ReconstructionPipeline()
    prev = pipe.run(base, meta=meta)

    # empty delta, no deletes: the merged run IS the previous run
    inc, folded = pipe.run_incremental(prev, base, None, meta=meta)
    assert inc.stats["incremental"] is True
    _assert_result_identical(inc, pipe.run(folded, meta=meta))
    np.testing.assert_array_equal(
        np.asarray(inc.comp_sorted), np.asarray(prev.comp_sorted)
    )

    # deletes only: filtered base run, renumbered rows
    keep = rng.random(n) > 0.2
    inc2, folded2 = pipe.run_incremental(prev, base, None, keep_rows=keep, meta=meta)
    assert folded2.n == int(keep.sum())
    _assert_result_identical(inc2, pipe.run(folded2, meta=meta))


def test_run_incremental_all_duplicate_keys(rng):
    """Degenerate keyset (empty D-bitmap, one-bit plan convention)."""
    n, nd = 64, 16
    words = np.full((n, 2), 7, np.uint32)
    base = KeySet(words=words, lengths=np.full(n, 8, np.int32),
                  rids=np.arange(n, dtype=np.uint32))
    meta = meta_from_keys(words)
    delta = KeySet(words=np.full((nd, 2), 7, np.uint32),
                   lengths=np.full(nd, 8, np.int32),
                   rids=np.arange(1000, 1000 + nd, dtype=np.uint32))
    pipe = ReconstructionPipeline()
    prev = pipe.run(base, meta=meta)
    inc, folded = pipe.run_incremental(prev, base, delta, meta=meta)
    assert inc.stats["incremental"] is True
    _assert_result_identical(inc, pipe.run(folded, meta=meta))


def test_run_incremental_falls_back_when_bitmap_grew(rng):
    from dataclasses import replace

    from repro.core.metadata import _set_bit

    base = _keyset(rng, 1000, w=2, mask=0xFF)
    meta = meta_from_keys(base.words)
    pipe = ReconstructionPipeline()
    prev = pipe.run(base, meta=meta)
    grown = replace(meta, dbitmap=_set_bit(meta.dbitmap, 2))
    delta = KeySet(
        words=base.words[:3] | np.uint32(1 << 29),
        lengths=np.full(3, 8, np.int32),
        rids=np.arange(9000, 9003, dtype=np.uint32),
    )
    inc, folded = pipe.run_incremental(prev, base, delta, meta=grown)
    assert inc.stats["incremental"] is False
    assert inc.stats["incremental_fallback"] == "dbitmap_changed"
    _assert_result_identical(inc, pipe.run(folded, meta=grown))


# ---------------------------------------------------------------------------
# ChangeLog semantics + serialization
# ---------------------------------------------------------------------------


def test_changelog_fold_replay_semantics():
    log = ChangeLog(n_words=2)
    base_rids = np.asarray([0, 1, 2, 3], np.uint32)
    k = lambda v: np.asarray([[v, v]], np.uint32)
    log.append_inserts(k(10), [10])          # plain insert, survives
    log.append_inserts(k(11), [11])          # insert then delete -> dead
    log.append_deletes([11])
    log.append_deletes([2])                  # base delete
    log.append_deletes([3])                  # base delete then reinsert:
    log.append_inserts(k(33), [3])           #   base row dead, insert lives
    keep, iw, il, ir = log.fold(base_rids)
    assert keep.tolist() == [True, True, False, False]
    assert ir.tolist() == [10, 3]
    assert iw[:, 0].tolist() == [10, 33]
    assert il.tolist() == [8, 8]
    assert len(log) == 6 and log.next_lsn == 6


def test_changelog_npz_roundtrip(tmp_path):
    rng = np.random.default_rng(0)
    log = ChangeLog(n_words=3, start_lsn=17)
    log.append_inserts(
        rng.integers(0, 2**32, size=(9, 3), dtype=np.uint32),
        np.arange(9, dtype=np.uint32),
        lengths=np.full(9, 10, np.int32),
    )
    log.append_deletes([4, 5])
    path = log.save(tmp_path / "log.npz")
    back = ChangeLog.load(path)
    assert back.n_words == 3 and back.start_lsn == 17
    assert back.next_lsn == log.next_lsn
    a, b = log.arrays(), back.arrays()
    for key in a:
        np.testing.assert_array_equal(a[key], b[key])


def test_changelog_empty_fold():
    log = ChangeLog(n_words=2)
    keep, iw, il, ir = log.fold(np.asarray([5, 6], np.uint32))
    assert keep.tolist() == [True, True] and iw.shape == (0, 2)


# ---------------------------------------------------------------------------
# Replica
# ---------------------------------------------------------------------------


def test_replica_matches_scratch_rebuild(rng):
    base = _keyset(rng, 3000, mask=0x0FFF00FF)
    rep = Replica(base)
    log = ChangeLog(3)
    ins = rng.integers(0, 2**32, size=(120, 3), dtype=np.uint32) & np.uint32(0x0FFF00FF)
    log.append_inserts(ins, np.arange(90_000, 90_120, dtype=np.uint32))
    log.append_deletes(np.arange(40, 80, dtype=np.uint32))
    st = rep.apply(log)
    assert st["n_delta"] == 120 and st["n_deleted"] == 40
    assert rep.applied_lsn == log.next_lsn - 1
    # the replica's index answers identically to a from-scratch rebuild of
    # the folded table under the replica's metadata
    scratch = ReconstructionPipeline().run(rep.keyset, meta=rep.meta)
    np.testing.assert_array_equal(
        np.asarray(rep.result.rid_sorted), np.asarray(scratch.rid_sorted)
    )
    found, rid = rep.search(ins[7])
    assert found and rid in range(90_000, 90_120)
    # deleted rid no longer reachable via its key unless duplicated
    assert rep.keyset.n == 3000 - 40 + 120


def test_replica_consecutive_batches_stay_incremental(rng):
    base = _keyset(rng, 4096, mask=0x00FF00FF)
    rep = Replica(base)
    lsn = 0
    n_inc = 0
    for b in range(3):
        log = ChangeLog(3, start_lsn=lsn)
        pick = rng.integers(0, rep.keyset.n, size=64)
        log.append_inserts(
            np.asarray(rep.keyset.words)[pick],
            np.arange(10_000 + 100 * b, 10_064 + 100 * b, dtype=np.uint32),
        )
        lsn = log.next_lsn
        st = rep.apply(log)
        n_inc += int(st["incremental"])
    # re-drawn existing keys add no distinction bits -> every batch merges
    assert n_inc == 3


# ---------------------------------------------------------------------------
# OnlineIndex incremental rebuild
# ---------------------------------------------------------------------------


def test_online_index_rebuild_incremental_and_correct(rng):
    from repro.core.index import OnlineIndex

    base = np.unique(
        rng.integers(0, 2**32, size=(400, 2), dtype=np.uint32) & np.uint32(0x0FFF0FFF),
        axis=0,
    )
    ks = KeySet(words=base, lengths=np.full(len(base), 8, np.int32),
                rids=np.arange(len(base), dtype=np.uint32))
    oi = OnlineIndex.build(ks)
    # duplicate existing keys: the insert rule sets no new bits
    dup = [base[i] for i in (3, 50, 99)]
    for j, k in enumerate(dup):
        oi.insert(k, rid=70_000 + j)
    oi.delete(base[10])
    oi2 = oi.rebuild()
    assert oi2.result.stats["incremental"] is True
    # the carried bitmap is pinned to the extraction bitmap, so a quiet
    # follow-up rebuild (even after the delete shed bits) merges again
    oi2b = oi2.rebuild()
    assert oi2b.result.stats["incremental"] is True
    assert oi2.keyset.n == len(base) + len(dup) - 1
    for j, k in enumerate(dup):
        found, rid = oi2.search(k)
        assert found
    found, _ = oi2.search(base[10])
    assert not found
    # a rebuild after a bit-growing insert falls back but stays correct
    newkey = base[0] | np.uint32(0x80000000)
    oi2.insert(newkey, rid=80_000)
    oi3 = oi2.rebuild()
    assert oi3.search(newkey) == (True, 80_000)


# ---------------------------------------------------------------------------
# checkpoint delta steps
# ---------------------------------------------------------------------------


def _tree():
    rng = np.random.default_rng(1)
    return {
        "wte": rng.normal(size=(16, 8)).astype(np.float32),
        "block": {"w1": rng.normal(size=(8, 8)).astype(np.float32),
                  "w2": rng.normal(size=(8,)).astype(np.float32)},
    }


def test_delta_checkpoint_roundtrip_and_chain(tmp_path):
    import jax

    from repro.ckpt.checkpoint import (
        restore_checkpoint,
        save_checkpoint,
        save_checkpoint_delta,
    )

    t1 = _tree()
    save_checkpoint(tmp_path, 1, t1)
    t2 = {"wte": t1["wte"] + 1, "block": dict(t1["block"])}
    save_checkpoint_delta(tmp_path, 2, t2, base_step=1)
    like = jax.tree_util.tree_map(np.zeros_like, t2)
    got, stats = restore_checkpoint(tmp_path, 2, like)
    for a, b in zip(jax.tree_util.tree_leaves(t2), jax.tree_util.tree_leaves(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # only changed keys move: the restore replays the log incrementally
    assert stats["incremental"] is True
    # chain: delta-on-delta with another changed leaf
    t3 = {"wte": t2["wte"], "block": {"w1": t2["block"]["w1"] * 2,
                                      "w2": t2["block"]["w2"]}}
    save_checkpoint_delta(tmp_path, 3, t3, base_step=2)
    got3, stats3 = restore_checkpoint(tmp_path, 3, like)
    for a, b in zip(jax.tree_util.tree_leaves(t3), jax.tree_util.tree_leaves(got3)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert stats3["meta"]["base_step"] == 2


def test_restore_checkpoint_backend_plumbed(tmp_path):
    import jax

    from repro.ckpt.checkpoint import restore_checkpoint, save_checkpoint

    t1 = _tree()
    save_checkpoint(tmp_path, 5, t1)
    like = jax.tree_util.tree_map(np.zeros_like, t1)
    _, stats = restore_checkpoint(tmp_path, 5, like, backend="pallas")
    assert stats["index_backend"] == "pallas"
    _, stats = restore_checkpoint(tmp_path, 5, like)
    assert stats["index_backend"] == "jnp"


# ---------------------------------------------------------------------------
# serving pager log replay
# ---------------------------------------------------------------------------


def test_pager_restart_replays_log(rng):
    from repro.serve.pager import PagedKVManager

    pm = PagedKVManager(n_pages=128, page_tokens=16)
    for s in range(6):
        pm.pages_for(s, 80)
    pm.rebuild_index()
    assert pm.stats["last_rebuild"]["incremental"] is False  # first build
    pm.free_seq(1)
    pm.pages_for(3, 160)  # extend an existing seq: no new key bits
    res = pm.rebuild_index()
    info = pm.stats["last_rebuild"]
    assert info["log_entries_replayed"] > 0
    # lookups agree with the table either way
    for (s, p), phys in list(pm._table.items()):
        assert pm.lookup(s, p) == phys
    assert pm.lookup(1, 0) is None
    # quiet restart folds an empty log through the merge path
    pm.rebuild_index()
    assert pm.stats["last_rebuild"]["incremental"] is True
    assert pm.stats["last_rebuild"]["log_entries_replayed"] == 0


def test_pager_realloc_of_mapped_slot_stays_consistent():
    """Re-alloc of an already-mapped (seq, page) must retire the old
    physical page in the log, or replay diverges from the table."""
    from repro.serve.pager import PagedKVManager

    pm = PagedKVManager(n_pages=32, page_tokens=8)
    for s in range(4):
        pm.alloc(s, 0)
    pm.rebuild_index()
    old = pm._table[(3, 0)]
    new = pm.alloc(3, 0)  # overwrite the mapping
    assert new != old
    res = pm.rebuild_index()
    assert res.comp_sorted.shape[0] == len(pm._table) == 4
    assert pm.lookup(3, 0) == new
    assert old in pm._free  # the retired page is allocatable again


# ---------------------------------------------------------------------------
# batched run_many on pallas (satellite)
# ---------------------------------------------------------------------------


def test_run_many_batched_on_pallas(rng):
    pipe = ReconstructionPipeline(backend="pallas")
    ref = ReconstructionPipeline(backend="jnp")
    keysets = [
        _keyset(rng, 900, mask=m) for m in (0x00FF0F0F, 0x0FF000FF, 0x000FFF0F)
    ]
    out = pipe.run_many(keysets)
    for ks, res in zip(keysets, out):
        assert res.stats.get("batched") == 3
        single = ref.run(ks)
        np.testing.assert_array_equal(
            np.asarray(res.rid_sorted), np.asarray(single.rid_sorted)
        )
        np.testing.assert_array_equal(
            np.asarray(res.comp_sorted), np.asarray(single.comp_sorted)
        )
