"""Paper Table 5 + Figure 11: parallelization speedup.

Each core count p runs in a subprocess with
``--xla_force_host_platform_device_count=p`` and times the distributed
sample sort (the row-column sort analogue) for full and compressed keys on
the INDBTAB stand-in.  Reports speedups vs p=1 and the compressed/full
total-time ratio per p (paper: ratio ~1.6 flat across p, near-linear
speedup to 16 cores)."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

from .common import emit, require_cpu_emulation

_WORKER = """
import json, time
import numpy as np, jax, jax.numpy as jnp
from repro.backends import get_backend
from repro.compat import make_mesh
from repro.configs.paper_index import DATASETS
from repro.core import dbits as D
from repro.data.synthetic import dataset_keys
from dataclasses import replace

p = len(jax.devices())
mesh = make_mesh((p,), ("data",))
cfg = replace(DATASETS["INDBTAB"], n_keys=131072)
ks = dataset_keys(cfg, seed=0)
n = (ks.n // p) * p
words = jnp.asarray(ks.words[:n]); rows = jnp.arange(n, dtype=jnp.uint32)
from repro.core.metadata import meta_from_keys
plan = meta_from_keys(np.asarray(words)).plan()

# the pipeline's distributed backend: extract runs before the all_to_all,
# so only compressed sort keys cross the (simulated) interconnect
be = get_backend("distributed", mesh=mesh)

def timeit(fn, *a, iters=3):
    fn(*a)  # warm
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter(); r = fn(*a)
        # device-side timing: block on the DistSortResult's arrays
        jax.block_until_ready((r.keys, r.rids, r.valid))
        ts.append(time.perf_counter() - t0)
    return sorted(ts)[len(ts)//2]

t_full = timeit(be.sample_sort_raw, words, rows)

comp = be.extract(words, plan)
t_extract_start = time.perf_counter()
comp2 = be.extract(words, plan); comp2.block_until_ready()
t_extract = time.perf_counter() - t_extract_start
t_comp = timeit(be.sample_sort_raw, comp, rows)

print(json.dumps({"p": p, "n": int(n), "t_full": t_full,
                  "t_extract": t_extract, "t_comp": t_comp}))
"""


def run(max_p: int = 4):
    require_cpu_emulation("table5")
    print("# Table 5 / Figure 11: parallel scaling (subprocess per core count)")
    print(f"# NOTE: this host has {os.cpu_count()} physical core(s); fake "
          "devices multiplex it, so 'speedup' here validates the harness + "
          "measures partition overhead, not real scaling (paper: 13.8x @ 16 real cores)")
    src = str(Path(__file__).resolve().parents[1] / "src")
    results = []
    p = 1
    while p <= max_p:
        env = dict(os.environ)
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={p}"
        env["PYTHONPATH"] = src
        r = subprocess.run(
            [sys.executable, "-c", textwrap.dedent(_WORKER)],
            capture_output=True, text=True, env=env, timeout=900,
        )
        if r.returncode != 0:
            print(f"# p={p} FAILED: {r.stderr[-400:]}")
            p *= 2
            continue
        d = json.loads(r.stdout.strip().splitlines()[-1])
        results.append(d)
        p *= 2
    base_full = results[0]["t_full"] if results else 1.0
    base_comp = results[0]["t_comp"] + results[0]["t_extract"] if results else 1.0
    for d in results:
        tot_comp = d["t_comp"] + d["t_extract"]
        derived = (
            f"n={d['n']};t_full={d['t_full']:.4f}s;"
            f"t_extract={d['t_extract']:.4f}s;t_comp_sort={d['t_comp']:.4f}s;"
            f"ratio={d['t_full'] / tot_comp:.2f};"
            f"speedup_full={base_full / d['t_full']:.2f};"
            f"speedup_comp={base_comp / tot_comp:.2f}"
        )
        emit(f"table5/cores_{d['p']}", tot_comp, derived)


if __name__ == "__main__":
    run()
