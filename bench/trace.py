"""Profiler trace -> device busy intervals, idle share, device time of a
stage, the busiest operations, and the longest idle gaps by what the host
was doing.

``load_xplane`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and
keeps only what the reductions need, as plain lists (``Trace``).  The
reductions work on that form alone, so the tests can check them on a
trace written by hand.

On a TPU the profiler gives each chip a plane ``/device:TPU:<i>``.  Its
line ``XLA Ops`` holds one event per operation run.  The benchmark's own host spans (``jax.profiler.TraceAnnotation``)
are the host events whose names start with ``bench.``.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
SPAN_PREFIX = "bench."


@dataclass
class Trace:
    """Events as ``[name, start_ns, end_ns]``; device events per chip."""

    ops: dict[int, list] = field(default_factory=dict)
    spans: list = field(default_factory=list)


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load_xplane(path: str) -> Trace:
    """The device operations of every TPU plane, and the
    benchmark's host spans."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    tr = Trace()
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name == "XLA Ops":
                    tr.ops[dev] = [[e.name, e.start_ns, e.end_ns]
                                   for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                tr.spans.extend([e.name, e.start_ns, e.end_ns]
                                for e in line.events
                                if e.name.startswith(SPAN_PREFIX))
    if not tr.ops:
        raise ValueError(f"{path}: no TPU device plane with XLA Ops")
    return tr


# ---------------------------------------------------------------------------
# reductions
# ---------------------------------------------------------------------------


def window(tr: Trace, name: str = "bench.window") -> tuple[float, float]:
    """Start and end (ns) of the one host span ``name``."""
    found = [s for s in tr.spans if s[0] == name]
    if len(found) != 1:
        raise ValueError(f"expected one {name!r} span, found {len(found)}")
    return float(found[0][1]), float(found[0][2])


def union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Union of ``[start, end]`` intervals clipped to ``[lo, hi]``."""
    out: list[list[float]] = []
    for s, e in sorted((max(float(s), lo), min(float(e), hi))
                       for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_s(tr: Trace, win: tuple[float, float]) -> float:
    """Seconds in which some operation ran on the device, averaged over
    the chips in the trace."""
    lo, hi = win
    per_chip = [sum(e - s for s, e in union(((o[1], o[2]) for o in ops), lo, hi))
                for ops in tr.ops.values()]
    return sum(per_chip) / len(per_chip) / 1e9


def idle_share(tr: Trace, win: tuple[float, float]) -> float:
    """1 - busy / window, as a fraction."""
    return 1.0 - busy_s(tr, win) / ((win[1] - win[0]) / 1e9)


def top_ops(tr: Trace, win: tuple[float, float], k: int = 10) -> list:
    """The ``k`` device operations (by name, summed over calls and chips)
    that took most time in the window: ``[[name, seconds], ...]``."""
    lo, hi = win
    acc: dict[str, float] = {}
    for ops in tr.ops.values():
        for name, s, e in ops:
            if e > lo and s < hi:
                acc[name] = acc.get(name, 0.0) + (min(e, hi) - max(s, lo)) / 1e9
    return [[n, v] for n, v in sorted(acc.items(), key=lambda x: -x[1])[:k]]


def idle_gaps(tr: Trace, win: tuple[float, float], k: int = 10) -> list:
    """The ``k`` longest stretches of the window in which chip 0 ran
    nothing, each named by the innermost benchmark span that covers its
    middle: ``[[span, seconds], ...]``."""
    lo, hi = win
    dev = min(tr.ops)
    busy = union(((o[1], o[2]) for o in tr.ops[dev]), lo, hi)
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:k]:
        mid = (s + e) / 2
        cover = [sp for sp in tr.spans if sp[1] <= mid <= sp[2]]
        name = min(cover, key=lambda sp: sp[2] - sp[1])[0] if cover else "none"
        out.append([name, (e - s) / 1e9])
    return out


def stage_busy_s(tr: Trace, win: tuple[float, float], within: str,
                 start: str, end: str) -> list[float]:
    """Device seconds of one pipeline stage in each ``within`` span: the
    busy time between the start of its first ``start`` span and the start
    of its first ``end`` span.  The pipeline waits for the device at each
    stage's end, so every operation of the stage runs in that stretch.
    A ``within`` span without both is an error."""
    lo, hi = win
    out = []
    for outer in (s for s in tr.spans if s[0] == within and lo <= s[1] < hi):
        inner = [s for s in tr.spans if outer[1] <= s[1] <= outer[2]]
        starts = [s[1] for s in inner if s[0] == start]
        ends = [s[1] for s in inner if s[0] == end]
        if not starts or not ends:
            raise LookupError(f"a {within!r} span without {start!r} and {end!r}")
        a, b = min(starts), min(ends)
        dev = min(tr.ops)
        out.append(sum(e - s for s, e in union(((o[1], o[2]) for o in tr.ops[dev]),
                                               a, b)) / 1e9)
    if not out:
        raise LookupError(f"no {within!r} span in the window")
    return out
