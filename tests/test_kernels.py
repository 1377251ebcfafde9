"""Per-kernel shape/dtype sweeps against the pure-jnp oracles
(interpret=True executes the Pallas kernel bodies on CPU)."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import compress as C
from repro.core import dbits as D
from repro.kernels.bitonic import ops as bitonic_ops
from repro.kernels.bitonic.ref import block_sort_ref
from repro.kernels.dbit import ops as dbit_ops
from repro.kernels.dbit.ref import adjacent_dbits_ref
from repro.kernels.pext import ops as pext_ops
from repro.kernels.pext.ref import pext_ref


def _keys(rng, n, w, mask=0xFFFFFFFF):
    return rng.integers(0, 2**32, size=(n, w), dtype=np.uint32) & np.uint32(mask)


@pytest.mark.parametrize("n", [64, 1000, 2048, 5000])
@pytest.mark.parametrize("w", [1, 3, 8])
def test_pext_kernel_sweep(rng, n, w):
    arr = _keys(rng, n, w, 0x3FC0FF03)
    jw = jnp.asarray(arr)
    bm = D.compute_dbitmap(jw)
    plan = C.make_plan(np.asarray(bm), w)
    got = pext_ops.pext(jw, plan, tile=256, interpret=True)
    want = pext_ref(jw, plan)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("tile", [128, 512, 1024])
def test_pext_tile_shapes(rng, tile):
    arr = _keys(rng, 777, 2, 0x00FFFF00)
    jw = jnp.asarray(arr)
    bm = D.compute_dbitmap(jw)
    plan = C.make_plan(np.asarray(bm), 2)
    got = pext_ops.pext(jw, plan, tile=tile, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(pext_ref(jw, plan)))


def test_pext_wide_keys(rng):
    """512-byte keys (the paper's ExURL max) = 128 words."""
    arr = _keys(rng, 300, 128, 0x01010101)
    jw = jnp.asarray(arr)
    bm = D.compute_dbitmap(jw)
    plan = C.make_plan(np.asarray(bm), 128)
    got = pext_ops.pext(jw, plan, tile=128, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(pext_ref(jw, plan)))


@pytest.mark.parametrize("n,w,block", [(512, 1, 128), (1024, 2, 256),
                                       (4096, 4, 512), (333, 3, 64)])
def test_bitonic_kernel_sweep(rng, n, w, block):
    arr = _keys(rng, n, w, 0xFFFF00FF)
    rids = np.arange(n, dtype=np.uint32)
    kw, kr = bitonic_ops.block_sort(
        jnp.asarray(arr), jnp.asarray(rids), block=block, interpret=True
    )
    kwn, krn = np.asarray(kw), np.asarray(kr)
    for s in range(0, n, block):
        e = min(s + block, n)
        blk = [tuple(r) for r in kwn[s:e]]
        assert blk == sorted(blk)
    assert sorted(map(tuple, kwn)) == sorted(map(tuple, arr))  # permutation
    assert (arr[krn] == kwn).all()  # payload follows keys


def test_bitonic_matches_ref_block_content(rng):
    n, w, block = 1024, 2, 256
    arr = _keys(rng, n, w, 0x0000FFFF)
    rids = np.arange(n, dtype=np.uint32)
    kw, _ = bitonic_ops.block_sort(
        jnp.asarray(arr), jnp.asarray(rids), block=block, interpret=True
    )
    rw, _ = block_sort_ref(jnp.asarray(arr), jnp.asarray(rids), block)
    np.testing.assert_array_equal(np.asarray(kw), np.asarray(rw))


def test_bitonic_duplicate_keys(rng):
    """Ties must neither drop nor duplicate payloads."""
    n, block = 512, 128
    arr = np.repeat(_keys(rng, n // 4, 2, 0x000000FF), 4, axis=0)
    rids = np.arange(n, dtype=np.uint32)
    kw, kr = bitonic_ops.block_sort(
        jnp.asarray(arr), jnp.asarray(rids), block=block, interpret=True
    )
    assert sorted(np.asarray(kr).tolist()) == rids.tolist()


@pytest.mark.parametrize("n,w", [(100, 1), (1500, 3), (4096, 8)])
def test_dbit_kernel_sweep(rng, n, w):
    arr = _keys(rng, n, w, 0x0FFFFFFF)
    (sw,) = D.sort_words(jnp.asarray(arr))
    got = dbit_ops.adjacent_dbits(sw, tile=256, interpret=True)
    want = adjacent_dbits_ref(sw)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_dbit_kernel_duplicates():
    arr = jnp.asarray(np.asarray([[1, 2], [1, 2], [1, 3]], np.uint32))
    got = np.asarray(dbit_ops.adjacent_dbits(arr, tile=128, interpret=True))
    assert got[0] == D.NO_DBIT  # equal adjacent keys
    assert got[1] == 63  # 2 vs 3 differ in the last bit of word 1


def test_kernel_pipeline_end_to_end(rng):
    """extract (pext kernel) -> block sort (bitonic) -> merge -> dbits
    (dbit kernel) reproduces the pure-jnp reconstruction pipeline."""
    n, w = 2048, 4
    arr = np.unique(_keys(rng, n, w, 0x00FF00FF), axis=0)
    jw = jnp.asarray(arr)
    bm = D.compute_dbitmap(jw)
    plan = C.make_plan(np.asarray(bm), w)
    comp_k = pext_ops.pext(jw, plan, tile=256, interpret=True)
    rids = jnp.arange(arr.shape[0], dtype=jnp.uint32)
    bw, br = bitonic_ops.block_sort(comp_k, rids, block=256, interpret=True)
    # final merge of block runs
    (ms, mr) = D.sort_words(bw, br)
    dp_k = dbit_ops.adjacent_dbits(ms, tile=256, interpret=True)
    # oracle pipeline
    comp_o = C.extract_bits(jw, plan)
    (so, ro) = D.sort_words(comp_o, rids)
    dp_o = adjacent_dbits_ref(so)
    np.testing.assert_array_equal(np.asarray(ms), np.asarray(so))
    np.testing.assert_array_equal(np.asarray(dp_k), np.asarray(dp_o))
