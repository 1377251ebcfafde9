"""``tools/span_breakdown.py``'s reductions, on traces written by hand."""

import importlib.util
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
_spec = importlib.util.spec_from_file_location(
    "span_breakdown", ROOT / "tools" / "span_breakdown.py")
sb = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(sb)
trace = sb.trace  # bench/trace.py


def _rebuild_trace():
    ops = {0: [["a", 10, 20], ["b", 25, 40], ["c", 60, 70], ["d", 85, 90]]}
    modules = {0: [["jit_sort(1)", 10, 20], ["jit_merge(2)", 25, 40],
                   ["jit_build_leaf(3)", 60, 70], ["jit_lookup(4)", 85, 90]]}
    spans = [["bench.window", 0, 100], ["bench.rebuild", 0, 80],
             ["repro.rebuild", 1, 79], ["repro.rebuild.upload", 2, 8],
             ["bench.backend.sort", 9, 11], ["repro.rebuild.sort", 9, 45],
             ["bench.backend.build", 55, 56], ["repro.rebuild.build", 52, 72],
             ["repro.rebuild.stats", 73, 78], ["bench.probe", 80, 95]]
    return trace.Trace(ops=ops, spans=spans), modules


def test_program_names():
    assert sb.program_name("jit_sort(12)") == "jit_sort"
    assert sb.program_name("jit_build_leaf") == "jit_build_leaf"
    assert sb.program_name("jit_merge.3") == "jit_merge"


def test_rebuild_breakdown_splits_the_sort_stage_by_program():
    b = sb.breakdown(*_rebuild_trace())
    assert b["modules"]["jit_merge"] == {"runs": 1, "device_s": pytest.approx(15e-9)}
    r = b["rebuild"]
    assert r["rebuilds"] == 1
    assert r["jit_sort"] == pytest.approx(10e-9)
    assert r["jit_merge"] == pytest.approx(15e-9)
    # the sort stage as sort_device_s reads it: the two programs, no more
    assert r["sort_device_s"] == pytest.approx(r["jit_sort"] + r["jit_merge"])
    assert r["repro.rebuild.upload"] == pytest.approx(6e-9)
    assert r["repro.rebuild.stats"] == pytest.approx(5e-9)
    assert b["lookup"] is None


def test_gaps_are_named_after_the_innermost_span_of_either_prefix():
    b = sb.breakdown(*_rebuild_trace())
    assert [[n, round(sec * 1e9)] for n, sec in b["idle_gaps"]] == [
        ["repro.rebuild", 20], ["repro.rebuild.stats", 15],
        ["repro.rebuild.upload", 10], ["bench.probe", 10],
        ["repro.rebuild.sort", 5]]
    assert sorted(b["idle_gaps"], key=lambda g: -g[1]) == b["idle_gaps"]
    assert b["idle_by_span"]["repro.rebuild.sort"] == pytest.approx(5e-9)
    assert b["idle_by_span"]["repro.rebuild.stats"] == pytest.approx(15e-9)
    assert b["idle_by_span"]["bench.probe"] == pytest.approx(10e-9)
    assert sum(b["idle_by_span"].values()) == pytest.approx(
        b["window_s"] - b["busy_s"])
    assert b["idle_in_shorter_gaps_s"] == pytest.approx(0.0)


def test_gaps_past_the_longest_are_counted_apart():
    b = sb.breakdown(*_rebuild_trace(), k=2)
    assert [[n, round(sec * 1e9)] for n, sec in b["idle_gaps"]] == [
        ["repro.rebuild", 20], ["repro.rebuild.stats", 15]]
    assert b["idle_in_shorter_gaps_s"] == pytest.approx(25e-9)


def test_a_rebuild_without_the_backend_spans_is_an_error():
    tr, modules = _rebuild_trace()
    tr.spans = [s for s in tr.spans if s[0] != "bench.backend.build"]
    with pytest.raises(LookupError):
        sb.breakdown(tr, modules)


def test_lookup_breakdown_leaves_the_copies():
    ms = 1_000_000
    spans = [["bench.window", 0, 100 * ms],
             ["bench.lookup", 10 * ms, 20 * ms], ["repro.snapshot.pin", 11 * ms, 12 * ms],
             ["repro.lookup", 13 * ms, 16 * ms],
             ["bench.lookup", 30 * ms, 50 * ms], ["repro.snapshot.pin", 31 * ms, 32 * ms],
             ["repro.lookup", 33 * ms, 40 * ms]]
    tr = trace.Trace(ops={0: [["x", 14 * ms, 18 * ms], ["y", 41 * ms, 45 * ms]]},
                     spans=spans)
    b = sb.breakdown(tr, {0: [["jit_lookup(7)", 14 * ms, 18 * ms],
                              ["jit_lookup(7)", 41 * ms, 45 * ms]]})
    lk = b["lookup"]
    assert lk["requests"] == 2
    assert lk["wall_ms"] == pytest.approx(15.0)
    assert lk["repro.snapshot.pin"] == pytest.approx(1.0)
    assert lk["repro.lookup"] == pytest.approx(5.0)
    assert lk["copies_ms"] == pytest.approx(9.0)
    assert b["rebuild"] is None
    assert b["modules"]["jit_lookup"]["runs"] == 2


def test_apply_breakdown_splits_each_apply_by_span_and_program():
    ops = {0: [["a", 10, 20], ["b", 30, 40], ["c", 60, 70], ["d", 80, 85]]}
    modules = {0: [["jit_merge(1)", 10, 20], ["jit_build_leaf(2)", 30, 40],
                   ["jit_sort(3)", 60, 70], ["jit_build_leaf(2)", 80, 85]]}
    spans = [["bench.window", 0, 100],
             ["bench.apply", 0, 45], ["repro.stream.decode", 1, 4],
             ["repro.replica.fold", 5, 7], ["bench.backend.merge_sorted", 9, 10],
             ["bench.backend.build", 29, 30],
             ["bench.apply", 50, 95], ["repro.stream.decode", 51, 56],
             ["repro.replica.fold", 57, 58], ["bench.backend.sort", 59, 60],
             ["bench.backend.build", 79, 80]]
    b = sb.breakdown(trace.Trace(ops=ops, spans=spans), modules)
    a = b["apply"]
    assert a["applies"] == 2
    assert a["wall_s"] == pytest.approx(45e-9)
    assert a["jit_merge"] == pytest.approx(5e-9)
    assert a["jit_sort"] == pytest.approx(5e-9)
    assert a["repro.stream.decode"] == pytest.approx(4e-9)
    assert a["repro.replica.fold"] == pytest.approx(1.5e-9)
    assert b["rebuild"] is None and b["lookup"] is None
