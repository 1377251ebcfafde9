"""Program spans and named plan-cache programs.

The rebuild and lookup paths write ``repro.*`` host spans into the
profiler's trace (``repro.core.spans``), and every plan-cache program is
named after its op family, so a device trace shows ``jit_sort``,
``jit_merge``, ``jit_lookup``, ... instead of one name for all.  Neither
may cost a retrace.
"""

import dataclasses
import glob
import inspect
import logging
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import plancache
from repro.core.keyformat import KeySet
from repro.core.pipeline import ReconstructionPipeline
from repro.core.snapshot import SnapshotCell

N = 3000
STAGES = ("upload", "extract", "sort", "build", "refresh", "stats")


def _keyset(n=N, seed=0):
    rng = np.random.default_rng(seed)
    words = rng.integers(0, 2**32, (n, 3), dtype=np.uint32) & np.uint32(0x00FF0F0F)
    return KeySet(words=words, lengths=np.full(n, 12, np.int32),
                  rids=np.arange(n, dtype=np.uint32))


def _pipe(backend="jnp"):
    # past the chunk threshold, so the sort runs chunk sorts and merges
    opts = {"interpret": True} if backend == "pallas" else None
    return ReconstructionPipeline(backend=backend, backend_opts=opts,
                                  chunk_threshold=1024, chunk_size=512)


def _rebuild_and_lookup(pipe, cell, ks):
    pipe.run(ks, publish_to=cell)
    with cell.pin() as pin:
        found, rid = pipe.backend.lookup(pin.snapshot.tree, ks.words[:5])
        return np.asarray(found), np.asarray(rid)


def _trace(log_dir):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return jax.profiler.trace(str(log_dir), profiler_options=opts)


def _host_spans(log_dir):
    path = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))[-1]
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith("repro.")]


def test_programs_are_named_after_their_op_family(caplog):
    ks = _keyset()
    with plancache.scoped_cache() as cache:
        pipe, cell = _pipe(), SnapshotCell()
        jax.config.update("jax_log_compiles", True)
        try:
            with caplog.at_level(logging.WARNING, logger="jax"):
                _rebuild_and_lookup(pipe, cell, ks)
        finally:
            jax.config.update("jax_log_compiles", False)
        by_op = {key[0]: (key, prog) for key, prog in cache.programs.items()}
        tree = cell.current.tree

    assert {"sort", "merge", "build_leaf", "build_level", "refresh_dbm",
            "lookup"} <= set(by_op)
    u32 = jnp.uint32
    (_, _, b, w, _), sort = by_op["sort"]
    text = sort.lower(jnp.zeros((b, w), u32), jnp.zeros((b,), u32),
                      np.uint32(b)).as_text()
    assert "module @jit_sort " in text
    (_, _, ba, bb, w, _), merge = by_op["merge"]
    text = merge.lower(jnp.zeros((ba, w), u32), jnp.zeros((ba,), u32),
                       jnp.zeros((bb, w), u32), jnp.zeros((bb,), u32),
                       np.uint32(ba), np.uint32(bb)).as_text()
    assert "module @jit_merge " in text
    (_, _, b, w), lookup = by_op["lookup"]
    text = lookup.lower(tree, jnp.zeros((b, w), u32), np.uint32(b)).as_text()
    assert "module @jit_lookup " in text

    compiled = set(re.findall(r"Compiling jit\((\w+)\)", caplog.text))
    assert {"sort", "merge", "build_leaf", "build_level", "refresh_dbm",
            "lookup"} <= compiled
    assert "traced" not in compiled


def test_unkeyed_program_takes_its_functions_name():
    cache = plancache.PlanCache()

    def double(x):
        return x * 2

    text = cache.jit(double).lower(jnp.arange(4)).as_text()
    assert "module @jit_double " in text
    assert cache.stats()["per_op"]["_unkeyed"]["traces"] == 1


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_spans_land_on_host_planes_nested_in_the_rebuild(tmp_path, backend):
    ks = _keyset()
    pipe, cell = _pipe(backend), SnapshotCell()
    _rebuild_and_lookup(pipe, cell, ks)  # compile outside the trace
    with _trace(tmp_path):
        found, rid = _rebuild_and_lookup(pipe, cell, ks)
    assert found.all() and (rid == ks.rids[:5]).all()

    spans = _host_spans(tmp_path)
    names = [s[0] for s in spans]
    for name in ["repro.rebuild", "repro.snapshot.publish", "repro.snapshot.pin",
                 "repro.lookup"] + [f"repro.rebuild.{s}" for s in STAGES]:
        assert names.count(name) == 1, (name, names)
    by_name = {s[0]: s for s in spans}
    _, lo, hi = by_name["repro.rebuild"]
    for name in [f"repro.rebuild.{s}" for s in STAGES] + ["repro.snapshot.publish"]:
        _, s, e = by_name[name]
        assert lo <= s <= e <= hi, name
    # the stages run in order, one after another
    starts = [by_name[f"repro.rebuild.{s}"][1] for s in STAGES]
    assert starts == sorted(starts)
    # the read comes after the rebuild and is no part of it
    assert by_name["repro.snapshot.pin"][1] >= hi
    assert by_name["repro.lookup"][1] >= by_name["repro.snapshot.pin"][2]


def test_incremental_rebuild_spans(tmp_path):
    ks = _keyset()
    pipe = _pipe()
    prev = pipe.run(ks)
    keep = np.ones(N, bool)
    keep[::7] = False
    pipe.run_incremental(prev, ks, keep_rows=keep)  # compile outside the trace
    with _trace(tmp_path):
        res, _ = pipe.run_incremental(prev, ks, keep_rows=keep)
    assert res.stats["incremental"] is True
    names = [s[0] for s in _host_spans(tmp_path)]
    for stage in ("filter", "merge", "build", "refresh", "stats"):
        assert names.count(f"repro.rebuild.{stage}") == 1, (stage, names)
    assert names.count("repro.rebuild") == 1


def test_incremental_fallback_nests_a_full_rebuild(tmp_path):
    ks = _keyset()
    pipe = _pipe()
    prev = dataclasses.replace(pipe.run(ks), extract_bitmap=None)
    pipe.run_incremental(prev, ks)  # compile outside the trace
    with _trace(tmp_path):
        res, _ = pipe.run_incremental(prev, ks)
    assert res.stats["incremental_fallback"] == "no_extract_bitmap"
    outer, inner = sorted((s for s in _host_spans(tmp_path) if s[0] == "repro.rebuild"),
                          key=lambda s: s[1])
    assert outer[1] <= inner[1] <= inner[2] <= outer[2]


def test_spanned_functions_keep_their_name_and_signature():
    run = ReconstructionPipeline.run
    assert run.__name__ == "run" and "publish_to" in inspect.signature(run).parameters
    assert SnapshotCell.acquire.__doc__.startswith("Pin the current snapshot")


@pytest.mark.parametrize("profiled", [False, True], ids=["off", "on"])
def test_warm_calls_with_spans_add_no_trace(tmp_path, profiled):
    ks = _keyset()
    with plancache.scoped_cache() as cache:
        pipe, cell = _pipe(), SnapshotCell()
        _rebuild_and_lookup(pipe, cell, ks)
        before = cache.stats()
        if profiled:
            with _trace(tmp_path):
                _rebuild_and_lookup(pipe, cell, ks)
        else:
            _rebuild_and_lookup(pipe, cell, ks)
        after = cache.stats()
    assert after["traces"] == before["traces"]
    assert after["misses"] == before["misses"]
    assert after["hits"] > before["hits"]
    assert after["per_op"].keys() == before["per_op"].keys()
