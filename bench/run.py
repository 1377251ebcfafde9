#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic and metrics are read from
``BENCHMARK.json`` at the root of the checkout.  Set-up (table, persisted
metadata, compile or cache load, one warm rebuild and lookup) runs first;
the window then runs for ``--seconds``; after it, the answers are compared
with the NumPy reference.  The last line of standard output is one JSON
object; the numbers compared, each with its limit, are the last lines of
standard error.  With ``--trace 1`` the window runs under the profiler
and the line carries the per-layer metrics instead of the end-to-end
ones.  Without a TPU, or with fewer chips than the cell asks for, it
exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness

    entry = harness.find(harness.load_spec(ROOT)["workloads"], args.workload,
                         "workload")
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: no TPU (JAX found {devices[0].platform}); the benchmark "
              "runs only on the chip", file=sys.stderr)
        return 2
    if len(devices) < int(entry["chips"]):
        print(f"bench: {args.workload} needs {entry['chips']} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 2

    from repro.core.plancache import enable_persistent_cache

    enable_persistent_cache(ROOT)
    result = harness.run_cell(args.workload, args.seed, args.seconds,
                              bool(args.trace), root=ROOT, t_start=T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
