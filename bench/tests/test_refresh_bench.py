"""The ``refresh_s`` reader on runs written by hand.  Run with
``PYTHONPATH=src python -m pytest bench/tests``.
"""

from __future__ import annotations

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, trace  # noqa: E402


def _run(rebuilds):
    tr = trace.Trace(ops={0: [["x", 0, 10]]}, spans=[["bench.window", 0, 100]])
    return harness.TracedRun(trace=tr, window=trace.window(tr), rebuilds=rebuilds,
                             requests=0, n_keys=1000, comp_words=2,
                             peaks={"hbm_bytes_per_s": 819e9})


def _read(run):
    return harness.load_module(str(ROOT), "metrics", "refresh_s").read(run)


def test_refresh_reader_averages_the_stage_wall():
    run = _run([{"epoch": 1, "timings": {"sort": 3.0, "refresh_meta": 0.5}},
                {"epoch": 2, "timings": {"sort": 5.0, "refresh_meta": 1.5}}])
    assert _read(run) == 1.0


def test_refresh_reader_skips_rebuilds_without_the_stage():
    run = _run([{"epoch": 1, "timings": {"refresh_meta": 0.25}},
                {"epoch": 2, "timings": {"sort": 5.0}}])
    assert _read(run) == 0.25
    assert _read(_run([])) is None
    assert _read(_run([{"timings": {"sort": 1.0}}])) is None
