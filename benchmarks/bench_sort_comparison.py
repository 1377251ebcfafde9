"""Paper Table 6 + Figure 13: row-column sort vs GCC STL parallel sort.

TPU analogue: our distributed sample sort (the row-column structure:
block sort -> splitter partition -> exchange -> merge) vs XLA's monolithic
``lax.sort`` of the same sharded operands (the "library sort" baseline).
Run at p=4 fake devices in a subprocess; also times the in-VMEM bitonic
block-sort kernel (interpret mode -> correctness-path timing only)."""

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
from repro.core import dbits as D

p = len(jax.devices())
mesh = make_mesh((p,), ("data",))
rng = np.random.default_rng(0)
n, W = 131072, 6  # 48B full sort keys, INDBTAB-like
words = jnp.asarray(rng.integers(0, 2**32, size=(n, W), dtype=np.uint32))
rids = jnp.arange(n, dtype=jnp.uint32)

def block(r):
    if hasattr(r, "keys"):  # DistSortResult: block on its device arrays
        jax.block_until_ready((r.keys, r.rids, r.valid))
    else:
        jax.block_until_ready(r)

def timeit(fn, *a, iters=3):
    block(fn(*a))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        block(fn(*a))
        ts.append(time.perf_counter() - t0)
    return sorted(ts)[len(ts)//2]

# library baseline: monolithic multiword lax.sort (sharded operands)
from jax.sharding import NamedSharding, PartitionSpec as P
sharded = jax.device_put(words, NamedSharding(mesh, P("data", None)))
lib = jax.jit(lambda w, r: D.sort_words(w, r))
t_lib = timeit(lib, sharded, rids)

# row-column analogue: the pipeline's distributed backend (sample sort,
# device-side — comparable to the sharded lax.sort baseline above)
be = get_backend("distributed", mesh=mesh)
t_rc = timeit(be.sample_sort_raw, words, rids)
print(json.dumps({"p": p, "t_library": t_lib, "t_rowcolumn": t_rc}))
"""


def run():
    require_cpu_emulation("table6")
    print("# Table 6 / Figure 13: row-column analogue vs monolithic lax.sort")
    src = str(Path(__file__).resolve().parents[1] / "src")
    for p in (1, 4):
        env = dict(os.environ)
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={p}"
        env["PYTHONPATH"] = src
        r = subprocess.run(
            [sys.executable, "-c", textwrap.dedent(_WORKER)],
            capture_output=True, text=True, env=env, timeout=900,
        )
        if r.returncode != 0:
            print(f"# p={p} FAILED: {r.stderr[-300:]}")
            continue
        d = json.loads(r.stdout.strip().splitlines()[-1])
        derived = (
            f"t_library={d['t_library']:.4f}s;t_rowcolumn={d['t_rowcolumn']:.4f}s;"
            f"rowcolumn_vs_library={d['t_library'] / d['t_rowcolumn']:.2f}x"
        )
        emit(f"table6/cores_{p}", d["t_rowcolumn"], derived)

    # bitonic VMEM block kernel (interpret mode: correctness-path timing)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.bitonic import ops as bit_ops

    from .common import timed

    rng = np.random.default_rng(0)
    words = jnp.asarray(rng.integers(0, 2**32, size=(4096, 2), dtype=np.uint32))
    rids = jnp.arange(4096, dtype=jnp.uint32)
    dt, _ = timed(
        lambda: bit_ops.block_sort(words, rids, block=512, interpret=True), iters=2
    )
    emit("table6/bitonic_block_kernel_interpret", dt,
         "n=4096;W=2;block=512;interpret=True")


if __name__ == "__main__":
    run()
