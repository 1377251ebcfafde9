"""One run of one cell: set-up, window, trace, comparison, result line.

Everything that belongs to a configuration, a traffic mix or a per-layer
metric is found by the name ``BENCHMARK.json`` gives it:
``bench/configs/<config>.json`` (its ``generator`` names
``bench/generators/<generator>.py``), ``bench/traffic/<traffic>.json``
(its ``driver`` names ``bench/drivers/<driver>.py``), and
``bench/metrics/<metric>.py``.
"""

from __future__ import annotations

import gc
import hashlib
import importlib.util
import json
import os
import shutil
import sys
import time
from dataclasses import dataclass

import numpy as np

from bench import reference, workload
from bench.peaks import device_peaks
from bench.systems import ProgramSystem
from bench.table import Table
from bench import trace as tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STATE_DIR = os.path.join("bench", ".state")


def load_spec(root: str) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def find(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def cell_metrics(spec: dict, key: str, cell: str) -> list[dict]:
    return [m for m in spec[key] if cell in m.get("workloads", [cell])]


def load_config(root: str, spec: dict, name: str) -> dict:
    with open(os.path.join(root, find(spec["configs"], name, "config")["file"])) as f:
        return json.load(f)


def load_module(root: str, kind: str, name: str):
    """``bench/<kind>/<name>.py`` of the checkout at ``root``, by path."""
    path = os.path.join(root, "bench", kind, f"{name}.py")
    mod_spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


class CompileClock:
    """Seconds spent compiling (or loading compiled programs from the
    persistent cache), how many programs, and how many came from that
    cache."""

    def __init__(self) -> None:
        import jax

        self.seconds = 0.0
        self.programs = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.programs += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def persisted_meta(root: str, cfg: dict, table: Table):
    """The DS-metadata a restart loads: the table's exact D-bitmap, its
    variant bits and a reference key.  It is computed by the reference
    (from the generator's sorted order where it has one) and kept under
    ``bench/.state`` per configuration and table, so a later run of the
    same table reads it back."""
    from repro.core.metadata import DSMeta

    key = hashlib.sha256(json.dumps([cfg, table.data_id], sort_keys=True)
                         .encode()).hexdigest()[:20]
    path = os.path.join(root, STATE_DIR, f"meta-{cfg['name']}-{key}.npz")
    if os.path.exists(path):
        with np.load(path) as z:
            dbitmap, varbitmap, refkey = z["dbitmap"], z["varbitmap"], z["refkey"]
    else:
        w = table.words
        sorted_words = (table.sorted_words if table.sorted_words is not None
                        else w[reference.ref_order(w)])
        dbitmap = reference.ref_dbitmap(sorted_words)
        varbitmap = np.bitwise_or.reduce(w ^ w[0], axis=0).astype(np.uint32)
        refkey = w[0].copy()
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = path + ".tmp.npz"
        np.savez(tmp, dbitmap=dbitmap, varbitmap=varbitmap, refkey=refkey)
        os.replace(tmp, path)
    return DSMeta(dbitmap=dbitmap, varbitmap=varbitmap, refkey=refkey,
                  n_words=int(table.words.shape[1]))


@dataclass
class TracedRun:
    """What a per-layer metric reader sees of a ``--trace 1`` run."""

    trace: tracing.Trace
    window: tuple  # (start_ns, end_ns) of the traced window
    rebuilds: list  # per rebuild: epoch, stage timings
    requests: int  # client requests answered
    n_keys: int
    comp_words: int  # compressed key width, 32-bit words
    peaks: dict


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _wrap_backend(backend) -> None:
    """Host spans around each call into the backend, for the traced run
    only: they place the device's idle gaps and the sort stage."""
    import jax

    for name in ("extract", "sort", "merge_sorted", "build", "refresh_meta", "lookup"):
        fn = getattr(backend, name)

        def spanned(*a, _fn=fn, _name=f"bench.backend.{name}", **k):
            with jax.profiler.TraceAnnotation(_name):
                return _fn(*a, **k)

        setattr(backend, name, spanned)


def run_cell(cell: str, seed: int, seconds: float, trace: bool, *,
             root: str = ROOT, t_start: float | None = None,
             overrides: dict | None = None, pipeline_opts: dict | None = None,
             system_factory=None) -> dict:
    """One run; returns the result line.  ``overrides`` (configuration
    keys) and ``pipeline_opts`` let a test run a cell at a tiny size with
    the Pallas interpreter, and ``system_factory`` puts the control in the
    program's place; a benchmark run passes none of them."""
    import jax

    t_start = time.perf_counter() if t_start is None else t_start
    spec = load_spec(root)
    entry = find(spec["workloads"], cell, "workload")
    cfg = {**load_config(root, spec, entry["config"]), **(overrides or {})}
    mix = workload.load_mix(root, entry["traffic"])
    gen = load_module(root, "generators", cfg["generator"])
    driver = load_module(root, "drivers", mix["driver"])
    clock = CompileClock()

    table = gen.make_table(cfg, seed)
    meta = persisted_meta(root, cfg, table)
    table.sorted_words = None  # only the metadata needed it
    _log(f"table: {table.n} keys x {table.words.shape[1]} words, "
         f"{meta.n_dbits} distinction bits -> {meta.plan().n_words_out} "
         f"compressed words")

    factory = system_factory or getattr(driver, "make_system", ProgramSystem)
    system = factory(table, meta, pipeline_opts)
    sess = driver.setup(system, table, meta, mix, np.random.default_rng([seed, 1]))

    trace_dir = os.path.join(root, STATE_DIR, "trace")
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        if isinstance(system, ProgramSystem):
            _wrap_backend(system.pipe.backend)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    compile_mark = (clock.seconds, clock.programs)
    setup_s = time.perf_counter() - t_start
    win = driver.window(system, sess, seconds)
    if trace:
        jax.profiler.stop_trace()
    in_window = clock.programs - compile_mark[1]
    _log(f"set-up {setup_s:.3f} s (compile {compile_mark[0]:.3f} s over "
         f"{compile_mark[1]} programs, {clock.cache_hits} from the persistent "
         f"cache); window {win.end - win.start:.3f} s, {len(win.rebuilds)} "
         f"rebuilds, {len(win.requests)} requests, {in_window} compiles")
    for f in win.failures[:3]:
        _log(f)

    dev = jax.devices()[0]
    n_chips = int(entry["chips"])
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": max(
                  int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                  for d in jax.devices()[:n_chips])}
    system.close()
    del system
    gc.collect()

    result = {"correct": False, "attempted": win.attempted,
              "failed": len(win.failures), "metrics": {}, "device": device}
    if trace:
        tr = tracing.load_xplane(tracing.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        w = tracing.window(tr)
        device["busy_s"] = tracing.busy_s(tr, w)
        device["window_s"] = (w[1] - w[0]) / 1e9
        run = TracedRun(trace=tr, window=w,
                        rebuilds=[{k: v for k, v in r.items()
                                   if k in ("epoch", "timings")}
                                  for r in win.rebuilds],
                        requests=len(win.requests), n_keys=table.n,
                        comp_words=meta.plan().n_words_out,
                        peaks=device_peaks(dev.device_kind) if dev.platform == "tpu" else {})
        for m in cell_metrics(spec, "per_layer", cell):
            value = load_module(root, "metrics", m["name"]).read(run)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
        result["breakdown"] = {"device_ops": tracing.top_ops(tr, w),
                               "idle_gaps": tracing.idle_gaps(tr, w)}
    else:
        values = {"setup_s": setup_s, **driver.values(sess, win)}
        for m in cell_metrics(spec, "end_to_end", cell):
            if m["name"] in values:  # absent only when every request failed
                result["metrics"][m["name"]] = {"value": values[m["name"]],
                                                "unit": m["unit"]}

    ref = reference.SortedTable(table.words, table.rids)
    compared = driver.compare(ref, sess, win)
    result["correct"] = all(v <= lim for _, v, lim in compared)
    result["compared"] = {n: {"value": v, "limit": lim} for n, v, lim in compared}
    for n, v, lim in compared:
        _log(f"compared {n}: {v} (limit {lim})")
    return result
