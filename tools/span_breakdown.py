#!/usr/bin/env python3
"""Break one traced benchmark window down by program span and program name.

    python tools/span_breakdown.py --workload <cell> --seed <n> --seconds <s> \\
        [--out FILE.json.gz]

Runs one cell of the chip benchmark (``bench/run.py``) with the profiler
on, exactly as ``--trace 1`` does.  Next to what the benchmark's loader
(``bench/trace.load_xplane``) keeps, it reads the two things that loader
leaves out: the program's ``repro.*`` host spans (``repro.core.spans``)
and each TPU plane's ``XLA Modules`` line, one event per program run.
The reductions are the benchmark's own (``bench/trace``), given the
spans of both prefixes.  Prints the harness's result line, then one JSON
line:

- ``modules``: for each program name in the window, its runs and the
  union of its device intervals in seconds (``jit_sort``, ``jit_merge``,
  ...; ``jit_traced`` only from a program that names none);
- ``rebuild`` (back-to-back rebuild cells): per ``bench.rebuild``, the
  union of ``jit_sort`` and of ``jit_merge`` intervals, ``sort_device_s``
  as the benchmark reads it, and the wall of each ``repro.rebuild.*`` and
  ``repro.snapshot.*`` span, as means;
- ``apply`` (catch-up cells): per ``bench.apply``, the union of
  ``jit_sort`` and of ``jit_merge`` intervals and the wall of each
  ``repro.stream.*``, ``repro.replica.*`` and ``repro.rebuild.*`` span,
  as means;
- ``lookup`` (request cells): per ``bench.lookup``, the wall of
  ``repro.snapshot.pin`` and ``repro.lookup`` in it and the rest (the
  query's copy in, the answers' copies out and the wait for the device),
  in milliseconds, as means;
- ``idle_gaps``, ``idle_by_span``: the longest stretches in which chip 0
  ran nothing, named as ``bench/trace.idle_gaps`` names them, now by the
  innermost span of either prefix; ``idle_in_shorter_gaps_s`` is the idle
  time of the gaps past the ``GAPS`` longest.

``--out`` keeps the spans, program intervals and chip 0's busy intervals
(gzip JSON) for a second look.  A TPU is needed, as for ``bench/run.py``.
"""

from __future__ import annotations

import argparse
import bisect
import gzip
import json
import os
import re
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import trace  # noqa: E402
from repro.core.spans import PREFIX  # noqa: E402

# gaps named one by one; in a read window of ~2,000 requests the 10,000
# longest hold all but a few hundredths of a percent of the idle time
GAPS = 10_000


def read_program(path: str) -> tuple[list, dict]:
    """What ``trace.load_xplane`` leaves out: the ``repro.*`` host spans,
    and each TPU plane's ``XLA Modules`` events, as ``[name, start_ns,
    end_ns]``."""
    from jax.profiler import ProfileData

    spans, modules = [], {}
    for plane in ProfileData.from_file(path).planes:
        m = trace.DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules[int(m.group(1))] = [[e.name, e.start_ns, e.end_ns]
                                                for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend([e.name, e.start_ns, e.end_ns]
                             for e in line.events if e.name.startswith(PREFIX))
    if not modules:
        raise ValueError(f"{path}: no TPU plane with an XLA Modules line")
    return spans, modules


def program_name(module: str) -> str:
    """``jit_sort(123)`` or ``jit_sort.4`` -> ``jit_sort``."""
    return re.match(r"[^(.\s]*", module).group(0)


def union_s(intervals, lo: float, hi: float) -> float:
    return sum(e - s for s, e in trace.union(intervals, lo, hi)) / 1e9


def module_table(modules: list, lo: float, hi: float) -> dict:
    """Per program name: runs and union device seconds in ``[lo, hi]``."""
    by_name: dict = {}
    for name, s, e in modules:
        if e > lo and s < hi:
            by_name.setdefault(program_name(name), []).append((s, e))
    return {n: {"runs": len(iv), "device_s": union_s(iv, lo, hi)}
            for n, iv in sorted(by_name.items(),
                                key=lambda kv: -union_s(kv[1], lo, hi))}


def span_rows(tr: trace.Trace, mods: list, win, outer: str) -> list[dict]:
    """Per ``outer`` span that starts in the window: its wall, the wall of
    each ``repro.*`` span inside, and the union of ``jit_sort`` and of
    ``jit_merge`` intervals, in seconds."""
    lo, hi = win
    rows = []
    for _, a, b in (s for s in tr.spans if s[0] == outer and lo <= s[1] < hi):
        row: dict = {"wall_s": (b - a) / 1e9}
        for name, s, e in tr.spans:
            if name.startswith(PREFIX) and a <= s and e <= b:
                row[name] = row.get(name, 0.0) + (e - s) / 1e9
        for prog in ("jit_sort", "jit_merge"):
            row[prog] = union_s([(s, e) for n, s, e in mods
                                 if program_name(n) == prog], a, b)
        rows.append(row)
    return rows


def means(rows: list[dict], count: str) -> dict | None:
    if not rows:
        return None
    keys = sorted({k for r in rows for k in r})
    return {count: len(rows),
            **{k: sum(r.get(k, 0.0) for r in rows) / len(rows) for k in keys}}


def rebuild_breakdown(tr: trace.Trace, mods: list, win) -> dict | None:
    """Means over the ``bench.rebuild`` spans that start in the window,
    with ``sort_device_s`` as the benchmark reads it."""
    rows = span_rows(tr, mods, win, "bench.rebuild")
    if rows:
        sort_device = trace.stage_busy_s(tr, win, "bench.rebuild",
                                         "bench.backend.sort", "bench.backend.build")
        for row, sort_s in zip(rows, sort_device):
            row["sort_device_s"] = sort_s
    return means(rows, "rebuilds")


def lookup_breakdown(spans: list, win) -> dict | None:
    """Means over the ``bench.lookup`` spans in the window, in ms.  A
    request's pin and lookup spans are the ones that start inside its
    ``bench.lookup``: the cell's one client thread sends one at a time."""
    lo, hi = win
    outers = [s for s in spans if s[0] == "bench.lookup" and lo <= s[1] and s[2] <= hi]
    if not outers:
        return None
    parts = ("repro.snapshot.pin", "repro.lookup")
    inner = sorted((s for s in spans if s[0] in parts), key=lambda s: s[1])
    starts = [s[1] for s in inner]
    acc = dict.fromkeys(("wall_ms", *parts, "copies_ms"), 0.0)
    for outer in outers:
        wall = (outer[2] - outer[1]) / 1e6
        got = dict.fromkeys(parts, 0.0)
        for name, s, e in inner[bisect.bisect_left(starts, outer[1]):
                                bisect.bisect_right(starts, outer[2])]:
            if e <= outer[2]:
                got[name] += (e - s) / 1e6
        acc["wall_ms"] += wall
        for k, v in got.items():
            acc[k] += v
        acc["copies_ms"] += wall - sum(got.values())
    return {"requests": len(outers), **{k: v / len(outers) for k, v in acc.items()}}


def breakdown(tr: trace.Trace, modules: dict, k: int = GAPS) -> dict:
    """``tr`` holds the spans of both prefixes; ``modules`` the programs'
    device intervals per chip."""
    win = trace.window(tr)
    lo, hi = win
    mods = modules.get(min(tr.ops), [])
    busy = trace.busy_s(tr, win)
    gaps = trace.idle_gaps(tr, win, k=k)
    by_span: dict = {}
    for name, sec in gaps:
        by_span[name] = by_span.get(name, 0.0) + sec
    return {"window_s": (hi - lo) / 1e9, "busy_s": busy,
            "modules": module_table(mods, lo, hi),
            "repro_spans": sum(s[0].startswith(PREFIX) for s in tr.spans),
            "rebuild": rebuild_breakdown(tr, mods, win),
            "apply": means(span_rows(tr, mods, win, "bench.apply"), "applies"),
            "lookup": lookup_breakdown(tr.spans, win),
            "idle_by_span": dict(sorted(by_span.items(), key=lambda kv: -kv[1])),
            "idle_in_shorter_gaps_s": (hi - lo) / 1e9 - busy - sum(by_span.values()),
            "idle_gaps": gaps[:15]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax

    if jax.devices()[0].platform != "tpu":
        print("span_breakdown: no TPU; it reads a chip's trace", file=sys.stderr)
        return 2
    from bench import harness
    from repro.core.plancache import enable_persistent_cache

    enable_persistent_cache(ROOT)
    kept: dict = {}
    load = trace.load_xplane

    def load_and_keep(path):  # the harness deletes the trace after loading it
        tr = load(path)
        spans, kept["modules"] = read_program(path)
        kept["trace"] = trace.Trace(ops=tr.ops, spans=tr.spans + spans)
        return tr

    trace.load_xplane = load_and_keep
    result = harness.run_cell(args.workload, args.seed, args.seconds, True,
                              root=ROOT, t_start=T_START)
    print(json.dumps(result), flush=True)
    tr = kept["trace"]
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        busy = trace.union(((o[1], o[2]) for o in tr.ops[min(tr.ops)]),
                           float("-inf"), float("inf"))
        with gzip.open(args.out, "wt") as f:
            json.dump({"spans": tr.spans, "modules": kept["modules"],
                       "busy": busy}, f)
    print(json.dumps(breakdown(tr, kept["modules"])), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
