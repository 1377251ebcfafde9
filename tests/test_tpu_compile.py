"""Compile every Pallas kernel of the main path for a described TPU v5e.

Nothing here runs on a chip: the TPU compiler is handed a described
``v5e:2x2`` topology and compiles each kernel with ``interpret=False`` at
recovery-scale widths — 9-word full keys (35-byte INDBTAB keys), 3-word
compressed keys, 2^20 lanes.  A kernel Mosaic refuses fails here, before
any chip time is spent on it.  Each compiled program must contain the
kernel itself (``tpu_custom_call``), not an XLA fallback.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.compress import make_plan
from repro.core.dbits import sort_words_keyed
from repro.kernels.bitonic import ops as bitonic_ops
from repro.kernels.bitonic.kernel import bitonic_block_sort_planes
from repro.kernels.build.kernel import pk_window_planes
from repro.kernels.dbit.kernel import dbit_planes
from repro.kernels.lookup.kernel import probe_planes, probe_planes_many
from repro.kernels.pext.kernel import pext_planes

FULL_WORDS = 9  # 35-byte keys
COMP_WORDS = 3
LANES = 1 << 20
PK_BITS = 16


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without the chip; keep it out of the cache."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(fn, *specs):
    text = jax.jit(fn).lower(*specs).compile().as_text()
    assert "tpu_custom_call" in text


def _plan():
    """A 9-word D-bitmap with 80 distinction bits: 3 compressed words."""
    bitmap = np.zeros((FULL_WORDS,), np.uint32)
    bitmap[:5] = np.uint32(0x0F0F0F0F)  # 16 bits per word
    plan = make_plan(bitmap, FULL_WORDS)
    assert plan.n_words_out == COMP_WORDS
    return plan


def test_pext_compiles(one_chip):
    plan = _plan()
    _assert_kernel(
        lambda p: pext_planes(p, plan, interpret=False),
        _spec((FULL_WORDS, LANES), jnp.uint32, one_chip),
    )


def test_bitonic_compiles(one_chip):
    _assert_kernel(
        lambda p: bitonic_block_sort_planes(p, COMP_WORDS, interpret=False),
        _spec((COMP_WORDS + 1, LANES), jnp.uint32, one_chip),
    )


def test_bitonic_vmapped_compiles(one_chip):
    """The pallas backend's batched sort program: block sort + run merge,
    vmapped over a stack of indexes."""

    def one(c, r):
        bk, brow = bitonic_ops.block_sort(c, r, interpret=False)
        return sort_words_keyed(bk, brow)

    _assert_kernel(
        jax.vmap(one),
        _spec((2, LANES, COMP_WORDS), jnp.uint32, one_chip),
        _spec((2, LANES), jnp.uint32, one_chip),
    )


def test_build_compiles(one_chip):
    _assert_kernel(
        lambda w, s: pk_window_planes(w, s, PK_BITS, interpret=False),
        _spec((FULL_WORDS, LANES), jnp.uint32, one_chip),
        _spec((LANES,), jnp.int32, one_chip),
    )


def test_probe_compiles(one_chip):
    _assert_kernel(
        lambda w, s, p: probe_planes(w, s, p, PK_BITS, interpret=False),
        _spec((FULL_WORDS, LANES), jnp.uint32, one_chip),
        _spec((LANES,), jnp.int32, one_chip),
        _spec((LANES,), jnp.uint32, one_chip),
    )


def test_probe_many_compiles(one_chip):
    t = 4
    _assert_kernel(
        lambda w, s, p: probe_planes_many(w, s, p, PK_BITS, interpret=False),
        _spec((t, FULL_WORDS, LANES // t), jnp.uint32, one_chip),
        _spec((t, LANES // t), jnp.int32, one_chip),
        _spec((t, LANES // t), jnp.uint32, one_chip),
    )


def test_dbit_compiles(one_chip):
    _assert_kernel(
        lambda a, b: dbit_planes(a, b, interpret=False),
        _spec((COMP_WORDS, LANES), jnp.uint32, one_chip),
        _spec((COMP_WORDS, LANES), jnp.uint32, one_chip),
    )
