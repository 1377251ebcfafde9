"""The chip benchmark's harness, generators, reference and trace reduction,
on the CPU at tiny sizes.

Runs drive the real program with the Pallas interpreter and small chunks
(passed here, never as an option of the benchmark), so the merge ladder
runs too, and every kept answer goes through the same comparison as on
the chip.  Run with ``PYTHONPATH=src python -m pytest bench/tests``.
"""

from __future__ import annotations

import json
import math
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, reference, systems, trace, workload, ycsb  # noqa: E402
from bench.generators import fixed_record, ycsb_hashed  # noqa: E402

TINY = {"indbtab-restart": {"n_keys": 8192}, "ycsb-c-read": {"recordcount": 8192}}
# the control runs no program, so it can run where its two cuts bite: more
# than 32 distinction bits and more than 2**16 records
CONTROL = {"indbtab-restart": {"n_keys": 70000}, "ycsb-c-read": {"recordcount": 70000}}
PIPELINE = {"backend": "pallas", "backend_opts": {"interpret": True},
            "chunk_size": 2048, "chunk_threshold": 4096}
RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device", "compared"]
SEED = 2**31 + 17  # seeds may be wider than 32 signed bits


def _run(cell, root=ROOT, seconds=0.3, sizes=TINY, **kw):
    return harness.run_cell(cell, SEED, seconds, kw.pop("trace", False),
                            root=str(root), overrides=sizes[cell],
                            pipeline_opts=PIPELINE, **kw)


# ---------------------------------------------------------------------------
# YCSB generators against per-key loops that follow the Java
# ---------------------------------------------------------------------------

M64 = 2**64


def _fnv_loop(val: int) -> int:
    h = ycsb.FNV_OFFSET_BASIS_64
    for _ in range(8):
        octet = val & 0xFF
        val >>= 8
        h = ((h ^ octet) * ycsb.FNV_PRIME_64) % M64
    if h >= 2**63:
        h -= M64
    return h if h == -(2**63) else abs(h)


def _zipf_loop(u: float) -> int:
    items = ycsb.ZIPF_ITEM_COUNT + 1
    theta = 0.99
    zeta2theta = 1 + math.pow(0.5, theta)
    alpha = 1 / (1 - theta)
    eta = (1 - math.pow(2.0 / items, 1 - theta)) / (1 - zeta2theta / ycsb.ZIPF_ZETAN)
    uz = u * ycsb.ZIPF_ZETAN
    if uz < 1.0:
        return 0
    if uz < 1.0 + math.pow(0.5, theta):
        return 1
    return int(items * math.pow(eta * u - eta + 1, alpha))


def test_fnvhash64_matches_loop():
    vals = np.concatenate([np.arange(2000), np.array([2**31 - 1, 2**40 + 7, 16_383_999])])
    want = [_fnv_loop(int(v)) for v in vals]
    assert ycsb.fnvhash64(vals).tolist() == want


def test_key_names_match_loop():
    keynums = np.concatenate([np.arange(3000), np.array([16_383_999])])
    rows, lengths = ycsb.key_names(keynums)
    for i, k in enumerate(keynums.tolist()):
        key = b"user" + str(_fnv_loop(k)).encode()
        assert lengths[i] == len(key)
        assert rows[i].tobytes() == key.ljust(ycsb.KEY_BYTES, b"\0")


def test_scrambled_zipfian_matches_loop():
    u = np.random.default_rng(5).random(5000)
    assert ycsb.zipfian_ranks(u).tolist() == [_zipf_loop(float(x)) for x in u]
    n = 1000
    got = ycsb.scrambled_zipfian(np.random.default_rng(9), n, 4000)
    rng, want = np.random.default_rng(9), []
    while len(want) < 4000:  # nextKeynum: redraw past the last record
        k = _fnv_loop(_zipf_loop(rng.random())) % (n + 1)
        if k < n:
            want.append(k)
    assert got.tolist() == want


# ---------------------------------------------------------------------------
# tables and the reference
# ---------------------------------------------------------------------------


def _lexsort_order(words):
    n, w = words.shape
    return np.lexsort((np.arange(n),) + tuple(words[:, i] for i in range(w - 1, -1, -1)))


def test_fixed_record_table_is_the_programs_indbtab_stand_in():
    import dataclasses

    from repro.configs.paper_index import DATASETS
    from repro.data.synthetic import dataset_keys

    t = fixed_record.make_table({"n_keys": 5000, "key_bytes": 35}, 11)
    ks = dataset_keys(dataclasses.replace(DATASETS["INDBTAB"], n_keys=5000), seed=11)
    np.testing.assert_array_equal(t.words, ks.words)
    np.testing.assert_array_equal(t.sorted_words, t.words[_lexsort_order(t.words)])


def test_ycsb_table_keys_are_distinct_and_packed_big_endian():
    t = ycsb_hashed.make_table({"recordcount": 4000}, 0)
    assert t.words.shape == (4000, 6)
    assert len(np.unique(reference.as_bytes(t.words))) == 4000
    key = reference.as_bytes(t.words[:1])[0].rstrip(b"\0")
    assert key == b"user" + str(_fnv_loop(0)).encode()


def test_reference_order_and_lookup():
    rng = np.random.default_rng(3)
    words = rng.integers(0, 4, (3000, 3), dtype=np.uint32)  # many equal keys
    np.testing.assert_array_equal(reference.ref_order(words), _lexsort_order(words))
    words = np.unique(words, axis=0)[rng.permutation(len(np.unique(words, axis=0)))]
    rids = rng.permutation(len(words)).astype(np.uint32)
    ref = reference.SortedTable(words, rids)
    q = np.concatenate([words[:5], np.full((2, 3), 9, np.uint32)])
    found, rid = ref.lookup(q)
    assert found.tolist() == [True] * 5 + [False] * 2
    assert rid.tolist() == rids[:5].tolist() + [0xFFFFFFFF] * 2


def test_reference_agrees_with_system_dbitmap_and_extraction():
    import jax.numpy as jnp

    from repro.core.compress import extract_bits, make_plan
    from repro.core.dbits import compute_dbitmap

    t = fixed_record.make_table({"n_keys": 3000, "key_bytes": 35}, 1)
    bitmap = reference.ref_dbitmap(t.words[reference.ref_order(t.words)])
    np.testing.assert_array_equal(bitmap, np.asarray(compute_dbitmap(jnp.asarray(t.words))))
    np.testing.assert_array_equal(
        reference.ref_extract(t.words, bitmap),
        np.asarray(extract_bits(jnp.asarray(t.words), make_plan(bitmap, 9))))


def test_absent_queries_miss():
    t = fixed_record.make_table({"n_keys": 4000, "key_bytes": 35}, 2)
    spec = {"keys": 64, "distribution": "uniform_distinct", "absent_share": 0.5, "sets": 3}
    q = workload.make_sets(t, np.random.default_rng(0), spec)
    assert q.shape == (3, 64, 9)
    found, _ = reference.SortedTable(t.words, t.rids).lookup(q.reshape(-1, 9))
    assert found.tolist() == ([True] * 32 + [False] * 32) * 3


# ---------------------------------------------------------------------------
# trace reduction
# ---------------------------------------------------------------------------


def _toy_trace():
    return trace.Trace(
        ops={0: [["x", 10, 20], ["y", 15, 30], ["z", 40, 45], ["x", 90, 120]]},
        spans=[["bench.window", 0, 100], ["bench.rebuild", 0, 60],
               ["bench.backend.sort", 5, 6], ["bench.backend.build", 35, 36],
               ["bench.probe", 50, 60]],
    )


def test_trace_reductions_on_a_toy_trace():
    tr = _toy_trace()
    win = trace.window(tr)
    assert win == (0.0, 100.0)
    assert trace.union([(10, 20), (15, 30), (40, 45), (90, 120)], *win) == [
        (10, 30), (40, 45), (90, 100)]
    assert trace.busy_s(tr, win) == pytest.approx(35e-9)
    assert trace.idle_share(tr, win) == pytest.approx(0.65)
    assert trace.top_ops(tr, win)[0] == ["x", pytest.approx(20e-9)]
    assert trace.idle_gaps(tr, win) == [["bench.window", pytest.approx(45e-9)],
                                        ["bench.backend.sort", pytest.approx(10e-9)],
                                        ["bench.backend.build", pytest.approx(10e-9)]]


# ---------------------------------------------------------------------------
# metric readers
# ---------------------------------------------------------------------------


def _traced_run(tr, **kw):
    base = dict(trace=tr, window=trace.window(tr), rebuilds=[], requests=0,
                n_keys=1000, comp_words=2, peaks={"hbm_bytes_per_s": 819e9})
    base.update(kw)
    return harness.TracedRun(**base)


def _reader(name):
    return harness.load_module(str(ROOT), "metrics", name).read


def test_stage_wall_readers():
    run = _traced_run(_toy_trace(), rebuilds=[
        {"epoch": 1, "timings": {"sort": 3.0, "build": 1.0}},
        {"epoch": 2, "timings": {"sort": 5.0, "build": 2.0}}])
    assert _reader("sort_s")(run) == 4.0
    assert _reader("build_s")(run) == 1.5
    assert _reader("sort_s")(_traced_run(_toy_trace())) is None


def test_sort_stage_readers():
    tr = _toy_trace()
    run = _traced_run(tr, rebuilds=[{"timings": {}}])
    assert trace.stage_busy_s(tr, run.window, "bench.rebuild", "bench.backend.sort",
                              "bench.backend.build") == [pytest.approx(20e-9)]
    assert _reader("sort_device_s")(run) == pytest.approx(20e-9)
    least = 2 * 1000 * (2 + 1) * 4
    assert _reader("sort_roofline")(run) == pytest.approx(100 * least / (20e-9 * 819e9))
    tr.spans = [s for s in tr.spans if s[0] != "bench.backend.build"]
    with pytest.raises(LookupError):
        _reader("sort_device_s")(run)
    assert _reader("sort_device_s")(_traced_run(tr)) is None


def test_lookup_device_reader():
    tr = _toy_trace()
    assert _reader("lookup_device_ms")(_traced_run(tr, requests=5)) == pytest.approx(
        1e3 * 35e-9 / 5)
    assert _reader("lookup_device_ms")(_traced_run(tr)) is None


def test_idle_readers():
    tr = _toy_trace()
    assert _reader("device_idle.ready")(_traced_run(tr, rebuilds=[{"timings": {}}])) \
        == pytest.approx(65.0)
    assert _reader("device_idle.lookup")(_traced_run(tr, requests=3)) == pytest.approx(65.0)
    assert _reader("device_idle.ready")(_traced_run(tr)) is None
    assert _reader("device_idle.lookup")(_traced_run(tr)) is None


# ---------------------------------------------------------------------------
# whole runs on the CPU
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("cell", sorted(TINY))
def test_cell_rehearsal_is_correct(cell):
    r = _run(cell)
    assert list(r) == RESULT_KEYS
    assert r["correct"] is True, r["compared"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert all(v["value"] == 0 for v in r["compared"].values())
    spec = harness.load_spec(str(ROOT))
    want = {m["name"] for m in harness.cell_metrics(spec, "end_to_end", cell)}
    assert set(r["metrics"]) == want and "setup_s" in want
    assert all(v["value"] > 0 for v in r["metrics"].values())


@pytest.mark.parametrize("cell", sorted(TINY))
def test_control_is_not_correct(cell):
    r = _run(cell, sizes=CONTROL, system_factory=systems.ControlSystem)
    assert r["correct"] is False
    assert any(v["value"] > v["limit"] for v in r["compared"].values())


def _restart_faults():
    from repro.backends.pallas_backend import PallasBackend
    from repro.core.pipeline import ReconstructionPipeline
    from repro.core.snapshot import SnapshotCell

    def state_unchanged(mp):  # a rebuild that publishes nothing new
        real = SnapshotCell.publish
        seen = []

        def publish(self, result, epoch=None):
            if not seen:
                seen.append(1)
                return real(self, result, epoch)

        mp.setattr(SnapshotCell, "publish", publish)

    def half_batch(mp):  # the sort leaves the second half of its rows out
        real = ReconstructionPipeline._sort_chunked

        def sort(self, comp, n, b, donate_sorts=False):
            ks, rs = real(self, comp, n // 2, b, donate_sorts)
            return ks, rs

        mp.setattr(ReconstructionPipeline, "_sort_chunked", sort)

    def answer_altered(mp):  # one record id flipped where the lookup makes it
        real = PallasBackend.lookup

        def lookup(self, tree, queries):
            found, rid = real(self, tree, queries)
            return found, rid.at[0].set(rid[0] ^ 1)

        mp.setattr(PallasBackend, "lookup", lookup)

    return {"state_unchanged": state_unchanged, "half_batch": half_batch,
            "answer_altered": answer_altered}


@pytest.mark.parametrize("fault", sorted(_restart_faults()))
def test_restart_fault_is_not_correct(monkeypatch, fault):
    _restart_faults()[fault](monkeypatch)
    r = _run("indbtab-restart")
    assert r["correct"] is False


def _read_faults():
    from repro.backends.pallas_backend import PallasBackend

    real = PallasBackend.lookup

    def state_unchanged(mp):  # every request gets the first request's answer
        first = []

        def lookup(self, tree, queries):
            if not first:
                first.append(real(self, tree, queries))
            return first[0]

        mp.setattr(PallasBackend, "lookup", lookup)

    def answer_altered(mp):  # one record id flipped where the lookup makes it
        def lookup(self, tree, queries):
            found, rid = real(self, tree, queries)
            return found, rid.at[0].set(rid[0] ^ 1)

        mp.setattr(PallasBackend, "lookup", lookup)

    return {"state_unchanged": state_unchanged, "answer_altered": answer_altered}


@pytest.mark.parametrize("fault", sorted(_read_faults()))
def test_read_fault_is_not_correct(monkeypatch, fault):
    """A YCSB read is one key, so a request has no half to leave out."""
    _read_faults()[fault](monkeypatch)
    assert _run("ycsb-c-read")["correct"] is False


_SCAN_DRIVER = """
import time

import numpy as np

from bench import workload
from bench.systems import ProgramSystem


class ScanSystem(ProgramSystem):
    def scan(self, k):  # the first k keys of the published index, in order
        with self.cell.pin() as pin:
            return np.asarray(pin.snapshot.tree.sorted_full[:k])


def make_system(table, meta, pipeline_opts):
    return ScanSystem(table, meta, pipeline_opts)


def setup(system, table, meta, mix, rng):
    system.rebuild(0)
    system.scan(int(mix["scan_keys"]))
    return {"table": table, "k": int(mix["scan_keys"])}


def window(system, sess, seconds):
    win = workload.Window()
    with workload.annotate("bench.window"):
        win.start = time.perf_counter()
        while time.perf_counter() - win.start < seconds:
            win.attempted += 1
            win.requests.append((0, 0.0, system.scan(sess["k"]), 0))
        win.end = time.perf_counter()
    return win


def values(sess, win):
    return {"scans_per_s": len(win.requests) / (win.end - win.start)}


def compare(ref, sess, win):
    want = sess["table"].words[ref.order[: sess["k"]]]
    wrong = sum(not np.array_equal(r[2], want) for r in win.requests)
    return [("scans_wrong", wrong, 0)]
"""


def test_data_driven_config_traffic_and_metric(tmp_path, monkeypatch):
    """A configuration, a traffic mix with an operation no existing driver
    has (an ordered scan), and two metrics added as files and entries only,
    with no file of the harness edited, run."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".state", "__pycache__"))
    before = {p: p.read_bytes() for p in (tmp_path / "bench").rglob("*") if p.is_file()}
    spec = harness.load_spec(str(ROOT))
    spec["configs"].append({"name": "tiny", "source": "test", "file": "bench/configs/tiny.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "tiny-scan", "config": "tiny", "traffic": "scan",
                              "chips": 1, "why": "test"})
    spec["end_to_end"].append({"name": "scans_per_s", "unit": "scans/s", "better": "higher",
                               "bound": 0.05, "source": "host_clock",
                               "workloads": ["tiny-scan"]})
    spec["per_layer"].append({"name": "scan_count", "unit": "n", "better": "higher",
                              "source": "program_counter", "layer": "test",
                              "moves": "scans_per_s", "workloads": ["tiny-scan"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    (tmp_path / "bench/configs/tiny.json").write_text(json.dumps(
        {"name": "tiny", "generator": "tiny_gen", "n_keys": 3000}))
    (tmp_path / "bench/generators/tiny_gen.py").write_text(
        "import numpy as np\n"
        "from bench.table import Table\n"
        "def make_table(cfg, seed):\n"
        "    n = cfg['n_keys']\n"
        "    w = np.stack([np.full(n, 7, np.uint32),\n"
        "                  np.random.default_rng(seed).permutation(n).astype(np.uint32)], 1)\n"
        "    return Table(words=w, lengths=np.full(n, 8, np.int32),\n"
        "                 rids=np.arange(n, dtype=np.uint32), data_id=str(seed))\n")
    (tmp_path / "bench/traffic/scan.json").write_text(json.dumps(
        {"driver": "scan", "scan_keys": 100}))
    (tmp_path / "bench/drivers/scan.py").write_text(_SCAN_DRIVER)
    (tmp_path / "bench/metrics/scan_count.py").write_text(
        "def read(run):\n    return float(run.requests)\n")
    monkeypatch.setattr(trace, "find_xplane", lambda d: d)
    # the CPU has no TPU plane to trace; hand the harness a device trace
    monkeypatch.setattr(trace, "load_xplane", lambda p: _toy_trace())
    r = harness.run_cell("tiny-scan", 5, 0.2, True, root=str(tmp_path),
                         pipeline_opts=PIPELINE)
    assert r["correct"] is True, r["compared"]
    assert r["metrics"]["scan_count"]["value"] >= 1
    assert "breakdown" in r and r["device"]["busy_s"] > 0
    r = harness.run_cell("tiny-scan", 5, 0.2, False, root=str(tmp_path),
                         pipeline_opts=PIPELINE)
    assert r["correct"] is True, r["compared"]
    assert set(r["metrics"]) == {"scans_per_s", "setup_s"}
    after = {p: p.read_bytes() for p in before}
    assert after == before  # no file that was there was edited


def test_run_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload", "indbtab-restart",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300, cwd=ROOT)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "no TPU" in r.stderr
