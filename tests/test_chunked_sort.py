"""Chunked large-N sort path: byte-identity vs the monolithic sort on
every backend, boundary sizes (2^k - 1, 2^k, 2^k + 1), cascade retrace
stability, the run_many batched grouping under per-op floors, and the
default that sorts a whole bucket in one program.

By default (``chunk_threshold=None``) a rebuild sorts its whole bucket in
one program; the ladder runs only under an explicit ``chunk_threshold``,
which the tests here scale far below production sizes so the cascade
runs in test time.
"""

import numpy as np
import pytest

from repro.core import plancache
from repro.core.keyformat import KeySet
from repro.core.metadata import meta_from_keys
from repro.core.pipeline import ReconstructionPipeline


def _keyset(rng, n, w=3, mask=0x0FFF00FF):
    words = rng.integers(0, 2**32, size=(n, w), dtype=np.uint32) & np.uint32(mask)
    rids = np.arange(n, dtype=np.uint32)
    rng.shuffle(rids)
    return KeySet(words=words, lengths=np.full(n, w * 4, np.int32), rids=rids)


@pytest.fixture(scope="module")
def rng():
    return np.random.default_rng(11)


def _assert_results_equal(a, b):
    np.testing.assert_array_equal(np.asarray(a.comp_sorted), np.asarray(b.comp_sorted))
    np.testing.assert_array_equal(np.asarray(a.row_sorted), np.asarray(b.row_sorted))
    np.testing.assert_array_equal(np.asarray(a.rid_sorted), np.asarray(b.rid_sorted))
    np.testing.assert_array_equal(
        np.asarray(a.tree.sorted_full), np.asarray(b.tree.sorted_full)
    )
    assert a.tree.height == b.tree.height


@pytest.mark.parametrize("backend", ["jnp", "pallas", "distributed"])
@pytest.mark.parametrize("n", [2**12 - 1, 2**12, 2**12 + 1])
def test_chunked_byte_identical_to_monolithic(rng, backend, n):
    """The cascade fold must reproduce the monolithic sort bit-for-bit at
    the awkward boundary sizes (last chunk of 1, exact tiling, one short)
    on all three backends."""
    ks = _keyset(rng, n)
    meta = meta_from_keys(ks.words)
    mono = ReconstructionPipeline(backend=backend, chunk_threshold=1 << 30)
    chunked = ReconstructionPipeline(
        backend=backend, chunk_threshold=2048, chunk_size=1024
    )
    res_m = mono.run(ks, meta=meta)
    res_c = chunked.run(ks, meta=meta)
    assert res_m.stats["chunked"] == 0
    assert res_c.stats["chunked"] == -(-n // 1024)
    _assert_results_equal(res_m, res_c)


def test_chunked_full_keys_baseline(rng):
    """The uncompressed baseline takes the chunked path too."""
    n = 3000
    ks = _keyset(rng, n)
    mono = ReconstructionPipeline(backend="jnp", chunk_threshold=1 << 30)
    chunked = ReconstructionPipeline(
        backend="jnp", chunk_threshold=1024, chunk_size=512
    )
    res_m = mono.run(ks, full_keys=True)
    res_c = chunked.run(ks, full_keys=True)
    assert res_c.stats["chunked"] == -(-n // 512)
    _assert_results_equal(res_m, res_c)


def test_chunked_warm_zero_retrace(rng):
    """A warm chunked rebuild replays entirely from the program cache:
    chunk sorts, cascade merges, build levels, refresh — zero traces."""
    plancache.reset_cache()
    pipe = ReconstructionPipeline(
        backend="jnp", chunk_threshold=2048, chunk_size=1024
    )
    ks = _keyset(rng, 5000)
    meta = meta_from_keys(ks.words)
    pipe.run(ks, meta=meta)
    traced = plancache.get_cache().stats()["traces"]
    pipe.run(_keyset(rng, 5000), meta=meta)  # same n -> same chunking
    pipe.run(_keyset(rng, 4993), meta=meta)  # same buckets, drifted n
    assert plancache.get_cache().stats()["traces"] == traced


def test_chunk_size_must_be_power_of_two():
    with pytest.raises(ValueError, match="power of two"):
        ReconstructionPipeline(chunk_size=1000)


def test_chunked_preserves_tree_queries(rng):
    """End-to-end: lookups against a chunked-path tree answer exactly as
    against the monolithic tree."""
    from repro.backends import get_backend
    import jax.numpy as jnp

    n = 2**12 + 5
    ks = _keyset(rng, n)
    meta = meta_from_keys(ks.words)
    res = ReconstructionPipeline(
        backend="jnp", chunk_threshold=2048, chunk_size=1024
    ).run(ks, meta=meta)
    be = get_backend("jnp")
    queries = jnp.asarray(ks.words[:64], jnp.uint32)
    found, rid = be.lookup(res.tree, queries)
    assert bool(np.all(np.asarray(found)))
    np.testing.assert_array_equal(np.asarray(rid), np.asarray(ks.rids[:64]))


def test_distributed_batched_extract_sort_sharded_subprocess():
    """run_many's batch axis shards across the mesh: the sharded batched
    program must reproduce the per-index jnp results exactly."""
    import subprocess
    import sys
    import textwrap
    from pathlib import Path

    src = str(Path(__file__).resolve().parent.parent / "src")
    import os

    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = src
    code = textwrap.dedent("""
        import numpy as np
        from repro.core.keyformat import KeySet
        from repro.core.pipeline import ReconstructionPipeline
        rng = np.random.default_rng(3)
        def ks_of(seed, n=600, w=3):
            r = np.random.default_rng(seed)
            words = r.integers(0, 2**32, size=(n, w), dtype=np.uint32) & np.uint32(0x00FF0F0F)
            rids = np.arange(n, dtype=np.uint32); r.shuffle(rids)
            return KeySet(words=words, lengths=np.full(n, w * 4, np.int32), rids=rids)
        keysets = [ks_of(s) for s in range(8)]  # 8 % 4 == 0 -> sharded path
        dist = ReconstructionPipeline(backend="distributed")
        ref = ReconstructionPipeline(backend="jnp")
        outs = dist.run_many(keysets)
        refs = [ref.run(k) for k in keysets]
        assert all(o.stats.get("batched") == 8 for o in outs), [o.stats.get("batched") for o in outs]
        for o, r in zip(outs, refs):
            np.testing.assert_array_equal(np.asarray(o.comp_sorted), np.asarray(r.comp_sorted))
            np.testing.assert_array_equal(np.asarray(o.rid_sorted), np.asarray(r.rid_sorted))
        print("SHARDED RUN_MANY OK")
    """)
    r = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "SHARDED RUN_MANY OK" in r.stdout


def test_chunk_sorts_stay_bucket_shaped_with_tail_n_valid(rng):
    """Every chunk — the ragged tail included — feeds the sort a full
    chunk-bucket-shaped slice plus a dynamic ``n_valid``, so the tail
    replays the same cached program instead of eagerly slicing to its
    ragged length and re-padding (the extra copy the valid-count operand
    exists to avoid)."""
    c = 512
    ks = _keyset(rng, 3 * c + 37)
    meta = meta_from_keys(ks.words)
    pipe = ReconstructionPipeline("jnp", chunk_threshold=1024, chunk_size=c)
    calls = []
    orig = pipe.backend.sort

    def spy(keys, rows, **kw):
        calls.append((int(keys.shape[0]), kw.get("n_valid"), kw.get("keep_padded")))
        return orig(keys, rows, **kw)

    pipe.backend.sort = spy
    try:
        res = pipe.run(ks, meta=meta)
    finally:
        pipe.backend.sort = orig
    assert res.stats["chunked"] == 4
    assert calls == [(c, c, True)] * 3 + [(c, 37, True)]


def test_tune_chunking_measures_and_persists(rng):
    """tune_chunking probes inside a throwaway scoped cache (the serving
    cache's programs and counters stay untouched — the bench's cold walls
    must stay honest), returns a sane plan, and the pipeline adopts and
    surfaces it."""
    pipe = ReconstructionPipeline("jnp")
    before = plancache.get_cache().stats()
    plan = pipe.tune_chunking(candidates=(256, 512), ref_n=1 << 13, iters=2)
    assert plancache.get_cache().stats() == before

    assert plan.backend == "jnp"
    assert plan.chunk_size in (256, 512)
    assert plan.chunk_threshold >= 2 * plan.chunk_size or (
        plan.chunk_threshold == plan.ref_n
    )
    assert set(plan.sort_warm) == {256, 512}
    assert all(v > 0 for v in plan.sort_cold.values())

    assert pipe.chunk_size == plan.chunk_size
    assert pipe.chunk_threshold == plan.chunk_threshold
    assert pipe.chunk_plan is plan

    ks = _keyset(rng, 700)
    res = pipe.run(ks)
    assert res.stats["chunk_tuned"] is True
    assert res.stats["chunk_size"] == plan.chunk_size
    assert res.stats["chunk_threshold"] == plan.chunk_threshold


def test_auto_tune_triggers_lazily(rng):
    """auto_tune_chunks calibrates on the first run that crosses the
    threshold, once; the adopted plan governs the run that triggered it."""
    pipe = ReconstructionPipeline(
        "jnp", auto_tune_chunks=True, chunk_threshold=1024, chunk_size=512
    )
    small = _keyset(rng, 600)
    pipe.run(small)
    assert pipe.chunk_plan is None  # below threshold: no probe

    calls = []
    orig = pipe.tune_chunking

    def spy(**kw):
        calls.append(kw)
        return orig(candidates=(256, 512), ref_n=1 << 13)

    pipe.tune_chunking = spy
    try:
        big = _keyset(rng, 2048)
        res1 = pipe.run(big)
        res2 = pipe.run(big)
    finally:
        pipe.tune_chunking = orig
    assert len(calls) == 1  # calibrated once, then reused
    assert pipe.chunk_plan is not None
    assert res1.stats["chunk_tuned"] and res2.stats["chunk_tuned"]
    ref = ReconstructionPipeline("jnp").run(big)
    np.testing.assert_array_equal(
        np.asarray(res1.comp_sorted), np.asarray(ref.comp_sorted)
    )


@pytest.mark.parametrize("backend", ["jnp", "pallas", "distributed"])
@pytest.mark.parametrize("n", [2**12 - 1, 2**12 + 1])
def test_default_pipeline_sorts_whole_bucket(rng, backend, n):
    """A default-constructed pipeline sorts the whole bucket in one sort
    program and compiles no merge program, byte-identical to the ladder."""
    ks = _keyset(rng, n)
    meta = meta_from_keys(ks.words)
    with plancache.scoped_cache() as cache:
        res = ReconstructionPipeline(backend=backend).run(ks, meta=meta)
        per_op = cache.stats()["per_op"]
    assert res.stats["chunked"] == 0
    assert res.stats["chunk_threshold"] is None
    assert "cascade_merges" not in res.stats
    assert "merge" not in per_op
    ladder = ReconstructionPipeline(
        backend=backend, chunk_threshold=2048, chunk_size=1024
    ).run(ks, meta=meta)
    assert ladder.stats["cascade_merges"] == ladder.stats["chunked"] - 1 > 0
    _assert_results_equal(res, ladder)


@pytest.mark.parametrize("threshold, chunk_size", [(1024, 512), (2048, 256)])
def test_explicit_chunk_threshold_takes_the_ladder(rng, threshold, chunk_size):
    """An explicit ``chunk_threshold`` keeps the ladder past it, in
    ``chunk_size`` chunks, and one sort at or below it."""
    n = 3000
    ks = _keyset(rng, n)
    meta = meta_from_keys(ks.words)
    pipe = ReconstructionPipeline(
        "jnp", chunk_threshold=threshold, chunk_size=chunk_size
    )
    res = pipe.run(ks, meta=meta)
    assert res.stats["chunked"] == -(-n // chunk_size)
    assert res.stats["chunk_size"] == chunk_size
    assert res.stats["cascade_merges"] == res.stats["chunked"] - 1
    small = _keyset(rng, threshold)
    assert pipe.run(small).stats["chunked"] == 0
    _assert_results_equal(ReconstructionPipeline("jnp").run(ks, meta=meta), res)


def test_auto_tune_needs_an_explicit_threshold(rng):
    """With the default ``chunk_threshold=None`` no run chunks, so
    ``auto_tune_chunks`` never calibrates."""
    pipe = ReconstructionPipeline("jnp", auto_tune_chunks=True)
    pipe.tune_chunking = lambda **kw: pytest.fail("tuned without a ladder")
    res = pipe.run(_keyset(rng, 2048))
    assert pipe.chunk_plan is None
    assert res.stats["chunked"] == 0
    assert res.stats["chunk_tuned"] is False
