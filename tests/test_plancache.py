"""Plan cache + shape bucketing: byte-identity under padding, cache
hit/miss/trace accounting, and the bitmap shed policy.

The contract under test: bucketing is *invisible* in the output bytes —
padded sort/merge/build/refresh produce exactly what the unpadded
reference produces, across all backends, including at the awkward sizes
``2**k - 1, 2**k, 2**k + 1`` that straddle bucket boundaries — while the
compiled-program count stays fixed (a second call in the same bucket, at a
*different* size, must perform zero recompilations; the trace counter in
``repro.core.plancache`` increments only while JAX traces, so the
assertion is strong).
"""

import os
import re
import subprocess
import sys
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import plancache
from repro.core.dbits import merge_words_keyed, sort_words_keyed
from repro.core.keyformat import KeySet
from repro.core.metadata import meta_from_keys, meta_on_rebuild
from repro.core.pipeline import ReconstructionPipeline, fold_keyset

BACKENDS = ("jnp", "pallas", "distributed")


def _keyset(rng, n, w=3, mask=0x00FF0F0F, rid0=0):
    words = rng.integers(0, 2**32, size=(n, w), dtype=np.uint32) & np.uint32(mask)
    rids = np.arange(rid0, rid0 + n, dtype=np.uint32)
    return KeySet(words=words, lengths=np.full(n, w * 4, np.int32), rids=rids)


def _pipe(backend):
    opts = {"interpret": True} if backend == "pallas" else None
    return ReconstructionPipeline(backend=backend, backend_opts=opts)


def _assert_tree_equal(a, b):
    assert len(a.levels) == len(b.levels)
    np.testing.assert_array_equal(np.asarray(a.sorted_full), np.asarray(b.sorted_full))
    np.testing.assert_array_equal(np.asarray(a.sorted_rids), np.asarray(b.sorted_rids))
    for la, lb in zip(a.levels, b.levels):
        for k in la:
            np.testing.assert_array_equal(np.asarray(la[k]), np.asarray(lb[k]))
    for k in a.leaf:
        np.testing.assert_array_equal(np.asarray(a.leaf[k]), np.asarray(b.leaf[k]))


# ---------------------------------------------------------------------------
# bucket arithmetic
# ---------------------------------------------------------------------------


def test_bucket_is_power_of_two_with_floor():
    assert plancache.bucket(0) == plancache.BUCKET_MIN
    assert plancache.bucket(1) == plancache.BUCKET_MIN
    assert plancache.bucket(plancache.BUCKET_MIN) == plancache.BUCKET_MIN
    assert plancache.bucket(plancache.BUCKET_MIN + 1) == 2 * plancache.BUCKET_MIN
    for k in (9, 12, 16):
        assert plancache.bucket(2**k - 1) == 2**k
        assert plancache.bucket(2**k) == 2**k
        assert plancache.bucket(2**k + 1) == 2 ** (k + 1)


def test_cache_counters_hit_miss():
    cache = plancache.PlanCache()
    calls = []
    prog1 = cache.program(("op", 1), lambda: calls.append(1) or (lambda: 1))
    prog2 = cache.program(("op", 1), lambda: calls.append(2) or (lambda: 2))
    assert prog1 is prog2 and calls == [1]
    assert cache.stats()["misses"] == 1 and cache.stats()["hits"] == 1


def test_cache_lru_bound_and_eviction_counters():
    """The optional ``max_programs`` bound evicts least-recently-used
    programs and counts the victims (long-lived-server hygiene)."""
    cache = plancache.PlanCache(max_programs=2)
    built = []

    def make(tag):
        return lambda: built.append(tag) or (lambda: tag)

    cache.program(("a",), make("a"))
    cache.program(("b",), make("b"))
    cache.program(("a",), make("a2"))  # hit: refreshes a's recency
    cache.program(("c",), make("c"))   # evicts b (the LRU), not a
    st = cache.stats()
    assert st["programs"] == 2 and st["evictions"] == 1
    assert st["max_programs"] == 2
    assert built == ["a", "b", "c"]
    assert cache.program(("a",), make("a3"))() == "a"  # a survived
    assert cache.program(("b",), make("b2"))() == "b2"  # b rebuilds...
    assert built == ["a", "b", "c", "b2"]
    assert cache.stats()["evictions"] == 2  # ...evicting the next LRU (c)
    # unbounded cache never evicts
    unbounded = plancache.PlanCache()
    for i in range(64):
        unbounded.program(("k", i), make(i))
    assert unbounded.stats()["evictions"] == 0
    assert unbounded.stats()["programs"] == 64
    # reset zeroes counters but keeps the configured bound
    cache.reset()
    assert cache.stats() == {
        "programs": 0, "hits": 0, "misses": 0, "traces": 0,
        "evictions": 0, "max_programs": 2, "per_op": {},
    }


def test_cache_per_op_breakdown():
    """stats()["per_op"] splits hit/miss/trace counters by op family
    (key[0]), with tracings attributed to the op whose builder wrapped
    the program — so a bench can see *which* family retraced."""
    cache = plancache.PlanCache()

    def build_sq():
        return cache.jit(lambda x: x * x)

    sq = cache.program(("sq", 256), build_sq)
    cache.program(("sq", 256), build_sq)           # hit
    cache.program(("other", 1), lambda: (lambda: 0))
    sq(jnp.arange(4))                              # first call: one trace
    sq(jnp.arange(4))                              # replay: no trace
    st = cache.stats()["per_op"]
    assert st["sq"] == {"hits": 1, "misses": 1, "traces": 1}
    assert st["other"] == {"hits": 0, "misses": 1, "traces": 0}
    # aggregates stay the sums of the breakdown
    agg = cache.stats()
    assert agg["hits"] == sum(c["hits"] for c in st.values())
    assert agg["misses"] == sum(c["misses"] for c in st.values())
    assert agg["traces"] == sum(c["traces"] for c in st.values())
    # a jit outside any builder lands under "_unkeyed"
    free = cache.jit(lambda x: x + 1)
    free(jnp.arange(4))
    assert cache.stats()["per_op"]["_unkeyed"]["traces"] == 1


def test_cache_lru_bound_stays_correct_under_real_ops(rng):
    """A tightly bounded cache re-traces evicted programs but never
    answers wrong: padded sorts at many buckets stay byte-identical."""
    cache = plancache.PlanCache(max_programs=1)
    from repro.core.dbits import sort_words_keyed

    for n in (255, 300, 600, 257):
        keys = jnp.asarray(
            rng.integers(0, 2**32, size=(n, 2), dtype=np.uint32), jnp.uint32
        )
        rows = jnp.asarray(rng.permutation(n).astype(np.uint32))
        ks_ref, rs_ref = sort_words_keyed(keys, rows)
        ks, rs = plancache.sort_padded(keys, rows, cache=cache)
        np.testing.assert_array_equal(np.asarray(ks_ref), np.asarray(ks))
        np.testing.assert_array_equal(np.asarray(rs_ref), np.asarray(rs))
        assert cache.stats()["programs"] <= 1
    assert cache.stats()["evictions"] >= 1


def test_set_max_programs_global():
    plancache.reset_cache()
    try:
        plancache.set_max_programs(3)
        assert plancache.cache_stats()["max_programs"] == 3
    finally:
        plancache.set_max_programs(None)
        assert plancache.cache_stats()["max_programs"] is None
    # a zero bound is rejected, not silently floored at 1
    with pytest.raises(ValueError):
        plancache.set_max_programs(0)
    with pytest.raises(ValueError):
        plancache.PlanCache(max_programs=0)


def test_trace_counter_counts_traces_not_calls():
    cache = plancache.PlanCache()
    f = cache.jit(lambda x: x + 1)
    f(jnp.zeros((4,)))
    f(jnp.ones((4,)))  # same shape: replay, no trace
    assert cache.stats()["traces"] == 1
    f(jnp.zeros((8,)))  # new shape: one more trace
    assert cache.stats()["traces"] == 2


_CACHE_PROBE = """
import sys
import jax, jax.numpy as jnp
from repro.core.plancache import enable_persistent_cache
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
print(enable_persistent_cache(sys.argv[1]))
jax.jit(lambda x: x * 3 + 1)(jnp.arange(8)).block_until_ready()
"""


@pytest.mark.parametrize("from_env", [False, True])
def test_persistent_cache_location(tmp_path, from_env):
    """The compile cache goes where ``JAX_COMPILATION_CACHE_DIR`` says, else
    to the fixed ``<root>/.jax_cache`` (which git ignores) — never both.
    A subprocess, because the cache directory is process-global."""
    repo = Path(__file__).resolve().parents[1]
    root, env_dir = tmp_path / "checkout", tmp_path / "env_cache"
    root.mkdir()
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(repo / "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    r = subprocess.run([sys.executable, "-c", _CACHE_PROBE, str(root)],
                       capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode == 0, r.stderr
    want, other = (env_dir, root / ".jax_cache") if from_env else (root / ".jax_cache", env_dir)
    assert r.stdout.strip().splitlines()[-1] == str(want)
    assert any(want.iterdir())
    assert not other.exists()
    assert ".jax_cache/" in (repo / ".gitignore").read_text().split()


# ---------------------------------------------------------------------------
# padded ops == unpadded reference (the byte-identity invariant)
# ---------------------------------------------------------------------------


def test_sort_padded_matches_reference(rng):
    for n in (255, 256, 257, 511, 513):
        keys = jnp.asarray(
            rng.integers(0, 2**32, size=(n, 3), dtype=np.uint32) & np.uint32(0xFF0F),
            jnp.uint32,
        )
        rows = jnp.asarray(rng.permutation(n).astype(np.uint32))
        ks_ref, rs_ref = sort_words_keyed(keys, rows)
        ks_pad, rs_pad = plancache.sort_padded(keys, rows, cache=plancache.PlanCache())
        np.testing.assert_array_equal(np.asarray(ks_ref), np.asarray(ks_pad))
        np.testing.assert_array_equal(np.asarray(rs_ref), np.asarray(rs_pad))


def test_sort_padded_all_ones_real_keys_precede_pads(rng):
    # a real all-ones key collides with the pad sentinel; the reserved pad
    # row range must still break the tie in favour of the real row
    n = 300
    keys = jnp.full((n, 2), 0xFFFFFFFF, jnp.uint32)
    rows = jnp.arange(n, dtype=jnp.uint32)
    ks, rs = plancache.sort_padded(keys, rows, cache=plancache.PlanCache())
    np.testing.assert_array_equal(np.asarray(rs), np.arange(n, dtype=np.uint32))


def test_merge_padded_matches_reference(rng):
    for na, nb in ((255, 9), (256, 256), (257, 31), (100, 0), (0, 100)):
        ka = jnp.asarray(
            rng.integers(0, 2**16, size=(na, 2), dtype=np.uint32), jnp.uint32
        )
        kb = jnp.asarray(
            rng.integers(0, 2**16, size=(nb, 2), dtype=np.uint32), jnp.uint32
        )
        ra = jnp.arange(na, dtype=jnp.uint32)
        rb = jnp.arange(na, na + nb, dtype=jnp.uint32)
        ka, ra2 = sort_words_keyed(ka, ra)
        kb, rb2 = sort_words_keyed(kb, rb)
        mk_ref, mr_ref = merge_words_keyed(ka, ra2, kb, rb2)
        mk, mr = plancache.merge_padded(ka, ra2, kb, rb2, cache=plancache.PlanCache())
        np.testing.assert_array_equal(np.asarray(mk_ref), np.asarray(mk))
        np.testing.assert_array_equal(np.asarray(mr_ref), np.asarray(mr))


def test_merge_same_bucket_zero_retrace(rng):
    """The ROADMAP open item: drifting (na, nb) inside one bucket pair must
    not retrace the jnp merge."""
    cache = plancache.PlanCache()

    def merge_at(na, nb):
        ka, ra = sort_words_keyed(
            jnp.asarray(rng.integers(0, 2**16, size=(na, 2), dtype=np.uint32)),
            jnp.arange(na, dtype=jnp.uint32),
        )
        kb, rb = sort_words_keyed(
            jnp.asarray(rng.integers(0, 2**16, size=(nb, 2), dtype=np.uint32)),
            jnp.arange(na, na + nb, dtype=jnp.uint32),
        )
        return plancache.merge_padded(ka, ra, kb, rb, cache=cache)

    merge_at(1000, 100)
    t0 = cache.stats()["traces"]
    assert t0 >= 1
    merge_at(1010, 90)  # same (1024, 128) bucket pair
    merge_at(997, 127)
    assert cache.stats()["traces"] == t0, cache.stats()
    merge_at(2000, 100)  # crosses bucket_a: exactly the new programs trace
    assert cache.stats()["traces"] > t0


# ---------------------------------------------------------------------------
# bucket boundaries: full pipeline across backends (deterministic; the
# hypothesis property sweep lives in test_bucket_boundaries.py)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("off", [-1, 0, 1])
def test_boundary_pipeline_parity_all_backends(rng, off):
    """The whole reconstruction (sorted keys, rid permutation, tree levels,
    refreshed bitmap) is byte-identical across jnp, pallas and distributed
    at bucket-straddling sizes."""
    n = 512 + off
    ks = _keyset(rng, n)
    ref = _pipe("jnp").run(ks)
    for backend in BACKENDS[1:]:
        res = _pipe(backend).run(ks)
        np.testing.assert_array_equal(
            np.asarray(ref.comp_sorted), np.asarray(res.comp_sorted)
        )
        np.testing.assert_array_equal(
            np.asarray(ref.rid_sorted), np.asarray(res.rid_sorted)
        )
        _assert_tree_equal(ref.tree, res.tree)
        np.testing.assert_array_equal(ref.meta.dbitmap, res.meta.dbitmap)


# ---------------------------------------------------------------------------
# pipeline-level cache behaviour (the acceptance assertion)
# ---------------------------------------------------------------------------


def test_second_same_bucket_run_zero_recompiles(rng):
    pipe = _pipe("jnp")
    pipe.run(_keyset(rng, 700))
    s0 = plancache.cache_stats()
    pipe.run(_keyset(rng, 700))
    pipe.run(_keyset(rng, 690))  # drifted size, same bucket
    s1 = plancache.cache_stats()
    assert s1["traces"] == s0["traces"], (s0, s1)
    assert s1["hits"] > s0["hits"]


def test_second_same_bucket_run_incremental_zero_recompiles(rng):
    pipe = _pipe("jnp")
    base = _keyset(rng, 3000)
    delta = _keyset(rng, 150, rid0=3000)
    meta = meta_from_keys(np.concatenate([base.words, delta.words]))
    prev = pipe.run(base, meta=meta)
    res, _ = pipe.run_incremental(prev, base, delta, meta=meta)
    assert res.stats["incremental"] is True
    s0 = plancache.cache_stats()
    res2, _ = pipe.run_incremental(prev, base, delta, meta=meta)
    s1 = plancache.cache_stats()
    assert res2.stats["incremental"] is True
    assert s1["traces"] == s0["traces"], (s0, s1)


def test_incremental_bucketed_matches_full(rng):
    """Byte-identity of the bucketed delta merge against the bucketed full
    run at a boundary-straddling base size."""
    for backend in BACKENDS:
        pipe = _pipe(backend)
        base = _keyset(rng, 1023)
        delta = _keyset(rng, 65, rid0=1023)
        meta = meta_from_keys(np.concatenate([base.words, delta.words]))
        prev = pipe.run(base, meta=meta)
        folded = fold_keyset(base, None, delta)
        full = pipe.run(folded, meta=meta)
        inc, _ = pipe.run_incremental(prev, base, delta, meta=meta)
        assert inc.stats["incremental"] is True, backend
        np.testing.assert_array_equal(
            np.asarray(full.comp_sorted), np.asarray(inc.comp_sorted)
        )
        np.testing.assert_array_equal(
            np.asarray(full.rid_sorted), np.asarray(inc.rid_sorted)
        )
        _assert_tree_equal(full.tree, inc.tree)


def test_run_many_buckets_drifting_sizes(rng):
    """Keysets whose sizes drift within one bucket batch together and each
    member's result equals its own single run."""
    pipe = _pipe("jnp")
    sets = [_keyset(rng, n) for n in (900, 950, 1000)]
    singles = [pipe.run(s) for s in sets]
    manys = pipe.run_many(sets)
    assert manys[0].stats.get("batched") == 3
    for s, m in zip(singles, manys):
        np.testing.assert_array_equal(
            np.asarray(s.comp_sorted), np.asarray(m.comp_sorted)
        )
        np.testing.assert_array_equal(
            np.asarray(s.rid_sorted), np.asarray(m.rid_sorted)
        )


# ---------------------------------------------------------------------------
# vectorized refresh_meta (satellite)
# ---------------------------------------------------------------------------


def _meta_on_rebuild_loop_ref(comp_sorted, old_meta, ref_full_key):
    """The PR-2 per-position Python loop, kept as the test oracle."""
    from dataclasses import replace

    from repro.core.dbits import NO_DBIT, adjacent_dbit_positions

    d_off = old_meta.d_offset()
    dpos = np.asarray(adjacent_dbit_positions(jnp.asarray(comp_sorted, jnp.uint32)))
    valid = dpos != NO_DBIT
    full_pos = d_off[dpos[valid]]
    dbm = np.zeros_like(old_meta.dbitmap)
    for p in np.unique(full_pos):
        dbm[p // 32] |= np.uint32(1) << np.uint32(31 - p % 32)
    return replace(old_meta, dbitmap=dbm, refkey=np.asarray(ref_full_key, np.uint32))


def test_meta_on_rebuild_vectorized_matches_loop(rng):
    for n in (1, 2, 255, 257, 1000):
        ks = _keyset(rng, n)
        meta = meta_from_keys(ks.words)
        res = _pipe("jnp").run(ks, meta=meta)
        comp = np.asarray(res.comp_sorted)
        got = meta_on_rebuild(comp, meta, ks.words[0])
        want = _meta_on_rebuild_loop_ref(comp, meta, ks.words[0])
        np.testing.assert_array_equal(got.dbitmap, want.dbitmap)
        np.testing.assert_array_equal(got.refkey, want.refkey)


def _dup_keyset(rng, n, w=3):
    """Keys drawn from a pool of ~n/3 distinct keys: sorted runs hold
    equal adjacent keys (n=2 gives two equal keys, an empty D-bitmap)."""
    pool = _keyset(rng, max(n // 3, 1), w=w).words
    words = pool[rng.integers(0, len(pool), size=n)]
    return KeySet(words=words, lengths=np.full(n, w * 4, np.int32),
                  rids=np.arange(n, dtype=np.uint32))


def _wide_keyset(rng, n, w=3):
    """Random keys with up to 96 one-hot keys mixed in: their adjacent
    D-bits spread over every word, so the compressed run is several
    words wide and pairs first differ in each of them."""
    ks = _keyset(rng, n, w=w)
    m = min(n, 32 * w)
    pos = rng.choice(32 * w, size=m, replace=False)
    ks.words[:m] = 0
    ks.words[np.arange(m), pos // 32] = np.uint32(1) << (31 - pos % 32).astype(np.uint32)
    return ks


@pytest.mark.parametrize("n", [1, 2, 255, 257, 1000])
@pytest.mark.parametrize("path", ["run", "run_incremental"])
@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_refresh_dbitmap_program_matches_loop(rng, backend, path, n):
    """The refresh program's compressed D-bitmap, mapped through D-offset,
    is byte-identical to the per-position loop over the sorted run —
    through the pipeline, and on a bucket-shaped run whose lanes at and
    past ``n_valid`` hold arbitrary non-sentinel words.  The metadata
    also covers 40 deleted keys, so the refresh has stale bits to shed."""
    pipe = _pipe(backend)
    for ks in (_keyset(rng, n), _dup_keyset(rng, n), _wide_keyset(rng, n)):
        deleted = _wide_keyset(rng, 40).words
        meta = meta_from_keys(np.concatenate([ks.words, deleted]))
        if path == "run":
            res, folded = pipe.run(ks, meta=meta), ks
        else:
            k = n // 4
            base = KeySet(words=ks.words[: n - k], lengths=ks.lengths[: n - k],
                          rids=ks.rids[: n - k])
            delta = KeySet(words=ks.words[n - k:], lengths=ks.lengths[n - k:],
                           rids=ks.rids[n - k:]) if k else None
            prev = pipe.run(base, meta=meta)
            res, folded = pipe.run_incremental(prev, base, delta, meta=meta)
            assert res.stats["incremental"] is True
        comp = np.asarray(res.comp_sorted)
        want = _meta_on_rebuild_loop_ref(comp, meta, folded.words[0])
        np.testing.assert_array_equal(res.meta.dbitmap, want.dbitmap)
        np.testing.assert_array_equal(res.meta.refkey, want.refkey)

        b = plancache.bucket_for("refresh", n)
        padded = rng.integers(0, 2**32 - 1, size=(b, comp.shape[1]),
                              dtype=np.uint32)
        padded[:n] = comp
        cbm = plancache.adjacent_dbitmap_padded(
            jnp.asarray(padded), backend=backend, n_valid=n
        )
        got = meta_on_rebuild(None, meta, folded.words[0], comp_dbitmap=cbm)
        np.testing.assert_array_equal(got.dbitmap, want.dbitmap)


def test_refresh_program_has_no_gather_or_scatter(rng):
    """The refresh program is one OR-reduction: no gather, no scatter in
    its HLO, a (Wc,) uint32 result, and no retrace on a warm call in the
    same bucket."""
    cache = plancache.PlanCache()
    comp = rng.integers(0, 2**32, size=(700, 2), dtype=np.uint32)
    comp = comp[np.lexsort(comp.T[::-1])]
    wc = comp.shape[1]
    out = plancache.adjacent_dbitmap_padded(jnp.asarray(comp), cache=cache)
    assert out.shape == (wc,) and out.dtype == np.uint32
    ((key, prog),) = cache.programs.items()
    assert key[0] == "refresh_dbm"
    b = key[2]
    lowered = prog.lower(jnp.zeros((b, wc), jnp.uint32), np.uint32(b))
    op = re.compile(r"stablehlo\.(gather|scatter)\b| (gather|scatter)\(")
    for text in (lowered.as_text(), lowered.compile().as_text()):
        assert not op.search(text)
    result = lowered.out_info
    assert (result.shape, result.dtype) == ((wc,), jnp.uint32)

    t0 = cache.stats()["traces"]
    plancache.adjacent_dbitmap_padded(jnp.asarray(comp[:690]), cache=cache)
    padded = plancache.pad_tail(jnp.asarray(comp), b, 0)
    plancache.adjacent_dbitmap_padded(padded, cache=cache, n_valid=650)
    assert cache.stats()["traces"] == t0, cache.stats()


def test_meta_on_rebuild_rejects_bit_past_d_offset(rng):
    """A compressed D-bit with no D-offset entry is refused, not mapped."""
    ks = _keyset(rng, 300)
    meta = meta_from_keys(ks.words)
    n_off = meta.d_offset().size
    cbm = np.zeros((n_off + 31) // 32 + 1, np.uint32)
    cbm[n_off // 32] |= np.uint32(1) << np.uint32(31 - n_off % 32)
    with pytest.raises(ValueError, match="D-offset"):
        meta_on_rebuild(None, meta, ks.words[0], comp_dbitmap=cbm)


# ---------------------------------------------------------------------------
# kernels/build pk-window gather (pallas) vs oracle
# ---------------------------------------------------------------------------


def test_build_kernel_pk_windows_matches_slice_bits(rng):
    from repro.core.btree import _slice_bits
    from repro.kernels.build import pk_windows
    from repro.kernels.build.ref import pk_windows_ref

    for m, w in ((37, 2), (512, 3), (513, 3)):
        words = rng.integers(0, 2**32, size=(m, w), dtype=np.uint32)
        starts = rng.integers(-4, w * 32 + 4, size=(m,)).astype(np.int32)
        for pk in (8, 16):
            want = np.asarray(
                _slice_bits(jnp.asarray(words), jnp.asarray(starts), pk)
            ).astype(np.uint32)
            got = np.asarray(
                pk_windows(jnp.asarray(words), jnp.asarray(starts), pk, interpret=True)
            )
            np.testing.assert_array_equal(want, got)
            np.testing.assert_array_equal(want, pk_windows_ref(words, starts, pk))


# ---------------------------------------------------------------------------
# bitmap shed policy (satellite, ROADMAP open item)
# ---------------------------------------------------------------------------


def _stale_bit_keyset():
    """Keys where one pair's distinction bit vanishes when the pair is
    deleted: rows 0/1 differ only at bit 31 of word 1; everyone else
    differs high in word 0."""
    words = np.zeros((6, 2), np.uint32)
    words[0] = (0, 0)
    words[1] = (0, 1)  # dbit(0, 1) = position 63
    for i in range(2, 6):
        words[i] = (i << 8, 0)
    return KeySet(
        words=words, lengths=np.full(6, 8, np.int32), rids=np.arange(6, dtype=np.uint32)
    )


def test_replica_shed_policy_threshold():
    from repro.replication import ChangeLog
    from repro.replication.replica import Replica

    ks = _stale_bit_keyset()

    # below threshold: bitmap stays pinned (stale bit 63 kept, incremental)
    rep = Replica(ks, shed_delete_frac=0.9)
    log = ChangeLog(2, start_lsn=0)
    log.append_deletes([0])
    stats = rep.apply(log)
    assert stats["shed_bits"] is False
    assert stats["incremental"] is True
    assert rep.meta.dbitmap[1] & np.uint32(1)  # bit 63 still pinned

    # above threshold: the refreshed bitmap is adopted and the stale bit
    # (only distinguishing the deleted pair) is gone
    rep2 = Replica(ks, shed_delete_frac=0.1)
    log2 = ChangeLog(2, start_lsn=0)
    log2.append_deletes([0, 1])
    stats2 = rep2.apply(log2)
    assert stats2["shed_bits"] is True
    assert stats2["deletes_since_shed"] == 0
    assert not (rep2.meta.dbitmap[1] & np.uint32(1))  # bit 63 shed

    # the post-shed batch pays one full rebuild (narrower projection), then
    # the replica answers byte-identically
    log3 = ChangeLog(2, start_lsn=log2.next_lsn)
    ins = np.asarray([[7 << 8, 0]], np.uint32)
    log3.append_inserts(ins, [100])
    stats3 = rep2.apply(log3)
    assert stats3["incremental"] is False
    assert stats3["fallback"] == "dbitmap_changed"
    found, rid = rep2.search(ins[0])
    assert found and rid == 100


def test_pager_shed_policy():
    from repro.serve.pager import PagedKVManager

    pg = PagedKVManager(n_pages=512, page_tokens=16, shed_delete_frac=0.25)
    for s in range(8):
        pg.pages_for(s, 16 * 8)  # 8 pages per seq
    pg.rebuild_index()
    assert pg._last_rebuild["shed_bits"] is False
    for s in range(4):
        pg.free_seq(s)
    pg.rebuild_index()  # 32 frees > 25% of 32 live keys -> shed
    assert pg._last_rebuild["shed_bits"] is True
    # lookups stay correct across the shed
    assert pg.lookup(5, 3) == pg._table[(5, 3)]
    assert pg.lookup(0, 0) is None
