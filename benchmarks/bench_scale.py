"""Million-key reconstruction scaling sweep (BENCH_scale.json).

The PR-6 claim measured: with in-program dynamic valid-count padding the
warm rebuild is a shape-stable replay (zero retraces, zero eager host
pads) at *every* size, and the chunked large-N sort path carries the same
property past the chunk threshold.  PR 7 adds the async overlapped path:
pipelines run with ``donate=True`` (zero-copy in-place chunk sorts, the
merge ladder dropping runs as they fold) and ``async_dispatch=True`` (one
end-of-run sync instead of per-stage barriers), so each cell now reports
the per-stage-synced warm wall *and* the async warm wall plus their
ratio.  A forced-chunked cell (``scale/<backend>/262144/chunked``) runs
the cascade with an explicit threshold (by default the pipeline sorts a
bucket whole) so CI can gate the chunked path at fast-suite sizes; the
full sweep additionally calibrates
``chunk_size``/``chunk_threshold`` per backend with
``tune_chunking`` (probes compile into a scoped throwaway cache, so the
serving cold walls stay honest).

Per (backend x size) cell: cold wall (pays every trace), warm per-stage
wall (median of ``iters``, barriers restored via ``stage_timings=True``),
async warm wall, warm trace count (asserted zero), peak device memory
where the platform reports it, achieved effective bandwidth against a
one-pass byte model, and — on a TPU only — the fraction of the chip's
published HBM bandwidth (``common.PEAKS``, keyed by ``device_kind``) that
bandwidth represents.  A CPU run leaves that field null.

Byte model (one pass per stage — a deliberate lower bound, so the
reported bytes/s never flatters):

  extract: read n*W*4, write n*Wc*4
  sort:    read + write n*(Wc+1)*4   (key words + the rid word)
  build:   read n*(Wc+W)*4, write ~n*(2+1+1)*4 leaf entry fields

  python -m benchmarks.run --only scale --json BENCH_scale.json
"""

from __future__ import annotations

import dataclasses
import time

import jax
import numpy as np

from repro.core import plancache
from repro.core.keyformat import KeySet
from repro.core.pipeline import ReconstructionPipeline
from .common import device_peaks, emit, timed

DEFAULT_SIZES = (65536, 262144, 1048576 + 4096)  # 64k -> 1M+ (off-boundary)

# the forced-chunked cell: small enough for the fast suite, large enough
# for a real (4-chunk) ladder
FORCED_CHUNK_N = 262144
FORCED_CHUNK_SIZE = 1 << 16
FORCED_CHUNK_THRESHOLD = 1 << 17


def _keyset(rng, n: int, n_words: int) -> KeySet:
    words = rng.integers(
        0, 2**32, size=(n, n_words), dtype=np.uint32
    ) & np.uint32(0x0FFF0FFF)
    return KeySet(
        words=words,
        lengths=np.full(n, n_words * 4, np.int32),
        rids=np.arange(n, dtype=np.uint32),
    )


def _stage_bytes(n: int, w: int, wc: int) -> dict[str, float]:
    return {
        "extract": n * 4.0 * (w + wc),
        "sort": n * 4.0 * 2 * (wc + 1),
        "build": n * 4.0 * (wc + w + 4),
    }


def _peak_device_mem() -> int | None:
    """Peak bytes in use on device 0, where the platform reports it
    (CPU's allocator usually doesn't — the column is then null)."""
    try:
        stats = jax.devices()[0].memory_stats()
    except Exception:
        return None
    if not stats:
        return None
    peak = stats.get("peak_bytes_in_use")
    return int(peak) if peak else None


def _measure_cell(
    pipe: ReconstructionPipeline,
    row_name: str,
    ks: KeySet,
    n_words: int,
    iters: int,
    assert_zero_warm_traces: bool,
) -> dict:
    n = ks.n
    t0 = time.perf_counter()
    res_cold = pipe.run(ks)
    cold_wall = time.perf_counter() - t0

    meta = res_cold.meta  # reuse: warm calls skip meta_from_keys
    # warm, per-stage barriers restored (the Figure-9 breakdown) — same
    # programs as the async replay, only the sync points differ
    t_warm_sync, res_sync = timed(
        lambda: pipe.run(ks, meta=meta, stage_timings=True),
        warmup=1, iters=iters,
    )
    # warm, async overlapped (the serving path): everything is compiled
    # by now, so these replays must not trace anything
    s0 = plancache.cache_stats()
    t_warm, res_warm = timed(
        lambda: pipe.run(ks, meta=meta), warmup=0, iters=iters
    )
    warm_traces = plancache.cache_stats()["traces"] - s0["traces"]

    warm = dict(res_sync.timings)
    wc = int(res_warm.comp_sorted.shape[1])
    bmodel = _stage_bytes(n, n_words, wc)
    total_bytes = sum(bmodel.values())
    stage_wall = warm["extract"] + warm["sort"] + warm["build"]
    achieved = total_bytes / max(stage_wall, 1e-9)
    dev = jax.devices()[0]
    roof = (
        achieved / device_peaks(dev.device_kind)["hbm_bytes_per_s"]
        if dev.platform == "tpu" else None
    )
    per_stage_bw = {k: bmodel[k] / max(warm[k], 1e-9) for k in bmodel}
    row = {
        "name": row_name,
        "backend": pipe.backend.name,
        "n_keys": n,
        "n_words": n_words,
        "comp_words": wc,
        "chunked": res_warm.stats["chunked"],
        "donate": res_warm.stats["donate"],
        "async_dispatch": True,
        "chunk_size": res_warm.stats["chunk_size"],
        "chunk_threshold": res_warm.stats["chunk_threshold"],
        "chunk_tuned": res_warm.stats["chunk_tuned"],
        "cold_wall_s": cold_wall,
        "warm_wall_s": t_warm,
        "warm_wall_sync_s": t_warm_sync,
        "async_speedup": t_warm_sync / max(t_warm, 1e-9),
        "warm": {
            k: warm[k]
            for k in ("extract", "sort", "build", "refresh_meta", "total")
        },
        "warm_traces": warm_traces,
        "peak_device_mem_bytes": _peak_device_mem(),
        "model_bytes": bmodel,
        "achieved_bytes_per_s": achieved,
        "hbm_roof_fraction": roof,
        "per_stage_bytes_per_s": per_stage_bw,
        "plan_cache": plancache.cache_stats(),
    }
    if res_warm.stats["chunked"]:
        row["cascade_peak_live_runs"] = res_warm.stats["cascade_peak_live_runs"]
        row["cascade_merges"] = res_warm.stats["cascade_merges"]
    emit(
        row_name,
        t_warm,
        f"cold={cold_wall:.3f}s;warm_async={t_warm:.4f}s;"
        f"warm_sync={t_warm_sync:.4f}s;async_x={row['async_speedup']:.3f};"
        f"sort={warm['sort']:.4f}s;build={warm['build']:.4f}s;"
        f"chunked={row['chunked']};traces={warm_traces};"
        f"GBps={achieved / 1e9:.2f};hbm_frac={roof}",
    )
    if assert_zero_warm_traces:
        assert warm_traces == 0, (
            f"{row_name}: warm run recompiled {warm_traces} programs"
        )
    return row


def run(
    sizes: tuple[int, ...] = DEFAULT_SIZES,
    backends: tuple[str, ...] = ("jnp", "pallas"),
    n_words: int = 3,
    iters: int = 3,
    assert_zero_warm_traces: bool = True,
    auto_tune: bool = False,
) -> list[dict]:
    print(
        f"# Scaling sweep: sizes={list(sizes)}, backends={list(backends)}, "
        f"auto_tune={auto_tune} (donate+async pipelines)"
    )
    rng = np.random.default_rng(0)
    rows: list[dict] = []
    for name in backends:
        pipe = ReconstructionPipeline(
            backend=name, donate=True, async_dispatch=True
        )
        if auto_tune:
            plan = pipe.tune_chunking(iters=2)
            print(
                f"# tuned {name}: chunk_size={plan.chunk_size} "
                f"chunk_threshold={plan.chunk_threshold}"
            )
        for n in sizes:
            ks = _keyset(rng, n, n_words)
            row = _measure_cell(
                pipe, f"scale/{name}/{n}", ks, n_words, iters,
                assert_zero_warm_traces,
            )
            if auto_tune:
                row["chunk_plan"] = dataclasses.asdict(pipe.chunk_plan)
            rows.append(row)

        # the forced-chunked cell: an explicit threshold, so the fast
        # suite (and CI) always exercises and gates the chunked path
        if FORCED_CHUNK_N in sizes:
            forced = ReconstructionPipeline(
                backend=name, donate=True, async_dispatch=True,
                chunk_threshold=FORCED_CHUNK_THRESHOLD,
                chunk_size=FORCED_CHUNK_SIZE,
            )
            ks = _keyset(rng, FORCED_CHUNK_N, n_words)
            rows.append(
                _measure_cell(
                    forced, f"scale/{name}/{FORCED_CHUNK_N}/chunked", ks,
                    n_words, iters, assert_zero_warm_traces,
                )
            )
    return rows


if __name__ == "__main__":
    run()
