"""The ``indbtab-catchup`` cell on the CPU at a tiny size: a rehearsal
that must come out correct, a control and faults that must not, and the
cell's metric readers on a trace written by hand.

Runs drive the program's replica path with the Pallas interpreter
(passed here, never as an option of the benchmark).  Run with
``PYTHONPATH=src python -m pytest bench/tests``.
"""

from __future__ import annotations

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, trace  # noqa: E402

CELL = "indbtab-catchup"
# at this size a batch's copies often need a distinction bit the table
# lacks, so applies fall back as well as merge
TINY = {"n_keys": 8192}
PIPELINE = {"backend": "pallas", "backend_opts": {"interpret": True}}
SEED = 2**31 + 29  # seeds may be wider than 32 signed bits
READERS = ("decode_s", "fold_s", "merge_s", "merge_device_s", "merge_roofline",
           "catchup_fallbacks", "device_idle.catchup")


def _run(**kw):
    return harness.run_cell(CELL, SEED, 0.3, False, root=str(ROOT), overrides=TINY,
                            pipeline_opts=PIPELINE, **kw)


def test_rehearsal_is_correct():
    r = _run()
    assert r["correct"] is True, r["compared"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert all(v["value"] == 0 for v in r["compared"].values())
    assert set(r["compared"]) == {
        "requests_failed", "rows_out_of_order", "compressed_keys_wrong", "rids_wrong",
        "tree_keys_wrong", "dbitmap_words_wrong", "extraction_bits_missing",
        "probe_answers_wrong", "probe_epochs_stale"}
    assert set(r["metrics"]) == {"ready_s", "setup_s"}
    assert all(v["value"] > 0 for v in r["metrics"].values())


def test_control_is_not_correct():
    """The control applies the inserts and drops the deletes."""
    driver = harness.load_module(str(ROOT), "drivers", "catchup_loop")
    r = _run(system_factory=driver.ControlReplica)
    assert r["correct"] is False
    assert r["compared"]["probe_answers_wrong"]["value"] > 0
    assert r["compared"]["rows_out_of_order"]["value"] > 0


def _faults():
    from repro.backends.pallas_backend import PallasBackend
    from repro.core.snapshot import SnapshotCell
    from repro.replication.replica import Replica

    def pre_apply_epoch(mp):  # only the bring-up is ever published
        real = SnapshotCell.publish
        seen = []

        def publish(self, result, epoch=None):
            if not seen:
                seen.append(1)
                return real(self, result, epoch)

        mp.setattr(SnapshotCell, "publish", publish)

    def insert_rule_dropped(mp):  # the metadata never gains an insert's bits
        mp.setattr(Replica, "_insert_rule", lambda self, words: self._meta)

    def answer_altered(mp):  # one record id flipped where the lookup makes it
        real = PallasBackend.lookup

        def lookup(self, tree, queries):
            found, rid = real(self, tree, queries)
            return found, rid.at[0].set(rid[0] ^ 1)

        mp.setattr(PallasBackend, "lookup", lookup)

    return {"pre_apply_epoch": pre_apply_epoch,
            "insert_rule_dropped": insert_rule_dropped,
            "answer_altered": answer_altered}


@pytest.mark.parametrize("fault", sorted(_faults()))
def test_fault_is_not_correct(monkeypatch, fault):
    _faults()[fault](monkeypatch)
    r = _run()
    assert r["correct"] is False
    if fault == "insert_rule_dropped":  # caught where it does its harm
        assert r["compared"]["extraction_bits_missing"]["value"] > 0


# ---------------------------------------------------------------------------
# metric readers
# ---------------------------------------------------------------------------


def _apply_trace():
    """Two applies: the first merges, the second fell back to a full
    rebuild (no ``merge_sorted`` span)."""
    return trace.Trace(
        ops={0: [["delta_sort", 5, 8], ["merge", 10, 20], ["gather", 22, 24],
                 ["build", 30, 40], ["sort", 55, 70], ["build", 75, 80]]},
        spans=[["bench.window", 0, 100],
               ["bench.apply", 0, 45], ["bench.backend.sort", 4, 5],
               ["bench.backend.merge_sorted", 9, 10], ["bench.backend.build", 28, 29],
               ["bench.probe", 42, 45],
               ["bench.apply", 50, 95], ["bench.backend.sort", 54, 55],
               ["bench.backend.build", 74, 75], ["bench.probe", 90, 95]])


def _traced_run(tr, rebuilds):
    return harness.TracedRun(trace=tr, window=trace.window(tr), rebuilds=rebuilds,
                             requests=0, n_keys=1000, comp_words=2,
                             peaks={"hbm_bytes_per_s": 819e9})


APPLIES = [{"epoch": 2, "timings": {"decode": 1.0, "fold": 0.5, "insert_rule": 0.1,
                                    "merge": 2.0, "build": 1.0}},
           {"epoch": 3, "timings": {"decode": 3.0, "fold": 1.5, "insert_rule": 0.1,
                                    "sort": 4.0, "build": 1.0}}]


def _reader(name):
    return harness.load_module(str(ROOT), "metrics", name).read


def test_catchup_readers_on_a_hand_written_trace():
    run = _traced_run(_apply_trace(), APPLIES)
    assert _reader("decode_s")(run) == 2.0
    assert _reader("fold_s")(run) == 1.0
    assert _reader("merge_s")(run) == 2.0  # the fallback has no merge
    assert _reader("catchup_fallbacks")(run) == 50.0
    # from the merge call to the build call of the apply that merged: the
    # merge program and the gather after it, not the delta sort before
    assert _reader("merge_device_s")(run) == pytest.approx(12e-9)
    least = 2 * 1000 * (2 + 1) * 4
    assert _reader("merge_roofline")(run) == pytest.approx(
        100 * least / (12e-9 * 819e9))
    busy = 3 + 10 + 2 + 10 + 15 + 5
    assert _reader("device_idle.catchup")(run) == pytest.approx(100 - busy)


def test_catchup_readers_without_applies_or_merges():
    empty = _traced_run(_apply_trace(), [])
    assert all(_reader(name)(empty) is None for name in READERS)
    # only fallbacks: no merge to read, every apply fell back
    tr = _apply_trace()
    tr.spans = [s for s in tr.spans if s[0] != "bench.backend.merge_sorted"]
    run = _traced_run(tr, [APPLIES[1]])
    assert _reader("merge_s")(run) is None
    assert _reader("merge_device_s")(run) is None
    assert _reader("merge_roofline")(run) is None
    assert _reader("catchup_fallbacks")(run) == 100.0
