# One function per paper table. Print ``name,us_per_call,derived`` CSV.
#
# ``--json PATH`` additionally writes the structured rows returned by suites
# that produce them (currently the per-backend pipeline suite) — the perf
# trajectory files, e.g.:
#
#   python -m benchmarks.run --only pipeline --fast --json BENCH_pipeline.json

from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma list: pipeline,incremental,build,lookup,"
                         "stream,serve,scale,table1,table2,table3,table4,"
                         "table5,table6,apps")
    ap.add_argument("--fast", action="store_true", help="smaller datasets")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write structured suite results (timings per stage "
                         "and backend) to PATH")
    args = ap.parse_args()

    from . import (
        bench_applications,
        bench_build,
        bench_construction,
        bench_datasets,
        bench_dbit_distribution,
        bench_incremental,
        bench_lookup,
        bench_multitenant,
        bench_parallel_scaling,
        bench_pipeline,
        bench_replication_stream,
        bench_scale,
        bench_serve,
        bench_sort_comparison,
        bench_zipf_sensitivity,
    )

    from repro.core.plancache import enable_persistent_cache

    enable_persistent_cache(Path(__file__).resolve().parents[1])

    scale = 0.05 if args.fast else 0.1
    suites = {
        "pipeline": lambda: bench_pipeline.run(scale=scale),
        "incremental": lambda: bench_incremental.run(
            n_base=8192 if args.fast else 65536
        ),
        "build": lambda: bench_build.run(
            n_keys=8192 if args.fast else 65536
        ),
        "lookup": lambda: bench_lookup.run(
            n_keys=8192 if args.fast else 65536,
            n_rebuilds=2 if args.fast else 4,
        ),
        "stream": lambda: bench_replication_stream.run(
            n_base=4096 if args.fast else 16384,
            batch_sizes=(64, 256) if args.fast else (64, 256, 1024),
            n_batches=4 if args.fast else 8,
        ),
        "serve": lambda: bench_serve.run(
            n_keys=8192 if args.fast else 16384,
            duration_s=1.5 if args.fast else 3.0,
            grid=((2, 64), (8, 64)) if args.fast else bench_serve.GRID,
        ),
        "multitenant": lambda: bench_multitenant.run(
            n_keys=1024 if args.fast else 4096,
            ts=(1, 8) if args.fast else bench_multitenant.TS,
        ),
        "scale": lambda: bench_scale.run(
            sizes=(65536, 262144) if args.fast else bench_scale.DEFAULT_SIZES,
            iters=2 if args.fast else 3,
            auto_tune=not args.fast,
        ),
        "table1": lambda: bench_construction.run(scale=scale),
        "table2": lambda: bench_datasets.run(scale=scale),
        "table3": bench_dbit_distribution.run,
        "table4": lambda: bench_zipf_sensitivity.run(
            n_keys=20000 if args.fast else 40000
        ),
        "table5": bench_parallel_scaling.run,
        "table6": bench_sort_comparison.run,
        "apps": bench_applications.run,
    }
    only = set(args.only.split(",")) if args.only else set(suites)
    unknown = only - set(suites)
    if unknown:
        ap.error(f"unknown suite(s): {','.join(sorted(unknown))} "
                 f"(choose from {','.join(suites)})")
    if args.json:
        # fail before spending minutes benchmarking, not after
        try:
            open(args.json, "a").close()
        except OSError as e:
            ap.error(f"cannot write --json target: {e}")
    payload: dict = {"suites": {}, "fast": args.fast}
    failed = []
    print("name,us_per_call,derived")
    for name, fn in suites.items():
        if name not in only:
            continue
        t0 = time.time()
        try:
            rows = fn()
            if isinstance(rows, list):
                payload["suites"][name] = rows
        except Exception:
            print(f"# SUITE {name} FAILED")
            traceback.print_exc()
            payload["suites"][name] = {"error": traceback.format_exc()}
            failed.append(name)
        print(f"# suite {name} took {time.time() - t0:.1f}s", flush=True)

    if args.json:
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
        print(f"# wrote {args.json}")
    if failed:
        print(f"# FAILED suites: {','.join(failed)}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
