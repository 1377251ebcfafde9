#!/usr/bin/env python3
"""Run a cell's control: the reference in the program's place, one step
below what the configuration states (``systems.ControlSystem``), through
the same set-up, window and comparison as a benchmark run.  Its numbers
must break their limits; they set the upper readings the limits sit
under.  The benchmark's own runs never run it.

    python bench/control.py --workload <cell> --seconds <s> --seeds <n> [<n> ...]

Prints one JSON line per seed: ``{"seed", "correct", "compared"}``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    from bench import harness, systems

    for seed in args.seeds:
        r = harness.run_cell(args.workload, seed, args.seconds, False, root=ROOT,
                             system_factory=systems.ControlSystem)
        print(json.dumps({"seed": seed, "correct": r["correct"],
                          "compared": r["compared"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
