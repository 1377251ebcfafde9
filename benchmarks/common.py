"""Shared benchmark utilities: timing, CSV emission, device peaks.

Every benchmark prints ``name,us_per_call,derived`` rows (harness contract).
"""

from __future__ import annotations

import time

import jax


def timed(fn, *args, warmup: int = 1, iters: int = 3):
    """Median wall time of fn(*args) with block_until_ready."""
    for _ in range(warmup):
        out = fn(*args)
        jax.tree_util.tree_map(
            lambda x: x.block_until_ready() if hasattr(x, "block_until_ready") else x,
            out,
        )
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.tree_util.tree_map(
            lambda x: x.block_until_ready() if hasattr(x, "block_until_ready") else x,
            out,
        )
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2], out


def emit(name: str, seconds: float, derived: str = ""):
    print(f"{name},{seconds * 1e6:.1f},{derived}", flush=True)


#: Published per-chip peaks, keyed by ``jax.Device.device_kind``.  Source:
#: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 393 TOP/s int8,
#: 16 GB HBM at 819 GB/s).
PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes": 16e9,
        "hbm_bytes_per_s": 819e9,
    },
}


def device_peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unknown kind is an error,
    never a default borrowed from another chip."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add them "
            "to benchmarks.common.PEAKS with their source"
        ) from None


def require_cpu_emulation(suite: str) -> None:
    """Refuse a suite that emulates several devices on the host CPU.

    Such a suite starts child processes with
    ``--xla_force_host_platform_device_count``, a CPU-only flag.  On an
    accelerator this process already holds the chip, so a child could not
    reach it.
    """
    platform = jax.default_backend()
    if platform != "cpu":
        raise RuntimeError(
            f"{suite} emulates devices on the host CPU "
            "(--xla_force_host_platform_device_count in child processes) and "
            f"cannot run on {platform}: this process holds the chip.  Run it "
            "with JAX_PLATFORMS=cpu."
        )
