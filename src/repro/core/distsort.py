"""Distributed compressed-key sort — the row-column sort on a TPU mesh.

The paper's row-column sort (Appendix A) structures a parallel sort as:
per-core cache-sized block sorts -> per-core multiway merge -> *perfect
p-partition* across cores -> per-core multiway merge.  On a TPU mesh the
same roles are played by:

  CPU core          -> mesh device (shard_map over one mesh axis)
  L3-sized block    -> VMEM tile   (``repro.kernels.bitonic`` block sort)
  per-core merge    -> on-device ``lax.sort`` of block-sorted runs
  perfect partition -> regular-sampling splitters + bucketed ``all_to_all``
  shared memory     -> ICI collective (this is the step whose byte volume
                       key compression divides by the sort-key ratio)

**Adaptation note** (recorded per DESIGN.md §2): the perfect partition of
Francis–Mathieson–Pannan yields *exactly* n/p elements per core, which
requires data-dependent shard sizes.  XLA SPMD programs have static shapes,
so we use sampled splitters with a *capacity factor* — each device accepts
up to ``ceil(n/p * capacity_factor)`` elements and the kernel reports
overflow (exactly the compromise MoE dispatch makes).  With regular
sampling of locally sorted runs, the imbalance bound is the classic sample
sort bound (< 2x for p samples/shard); capacity 1.5 has zero overflow in
all our benchmarks, and overflow is detected and surfaced, never silent.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from .dbits import sort_words, sort_words_keyed

__all__ = ["DistSortResult", "sample_sort", "make_sample_sort"]

# Padding sentinel: all-ones words sort after every real key under uint32
# lexicographic order.  Real keys that are all-ones in every word would tie
# with the sentinel; the validity mask (not the sentinel value) is
# authoritative, so correctness does not depend on sentinel uniqueness.
_SENTINEL = np.uint32(0xFFFFFFFF)


@dataclass
class DistSortResult:
    """Globally sorted keys, shard-padded.

    keys:     (p * cap, W) — device i holds rows [i*cap, (i+1)*cap); within a
              device rows are sorted and padded at the tail with sentinels.
              Concatenating the valid prefixes of shards 0..p-1 yields the
              globally sorted order.
    rids:     (p * cap,) permuted record ids (sentinel rows: 0xFFFFFFFF).
    valid:    (p * cap,) bool — True for real rows.
    overflow: () int32 — number of dropped elements (0 in healthy runs;
              callers must check and re-run with higher capacity if not).
    """

    keys: jnp.ndarray
    rids: jnp.ndarray
    valid: jnp.ndarray
    overflow: jnp.ndarray


def _local_shard_sort(words, rids):
    # Keyed sort: ties between equal keys break on the rid everywhere in
    # this module, so the global output order is the deterministic
    # (key, rid) order regardless of how the exchange interleaves equal
    # keys across shards.
    return sort_words_keyed(words, rids)


def make_sample_sort(mesh: Mesh, axis_name: str, n_per_shard: int, n_words: int,
                     capacity_factor: float = 1.5):
    """Build a jit-able distributed sample sort over one mesh axis.

    Returns fn(words (n,W) uint32, rids (n,)) -> DistSortResult with
    n = p * n_per_shard, sharded on axis 0.
    """
    p = mesh.shape[axis_name]
    cap = int(np.ceil(n_per_shard * capacity_factor / max(p, 1)))  # per-bucket
    recv = p * cap  # rows per device after exchange

    def shard_fn(words, rids):
        ln = words.shape[0]

        # ---- phase 0: spread exchange -----------------------------------
        # The paper scans an *unsorted* table; if the caller's shards are
        # range-partitioned (e.g. already sorted), every row of a shard
        # lands in one bucket and per-pair capacity blows up.  A fixed
        # block exchange gives every device a cross-section of the global
        # range first (one extra all_to_all of the payload).
        if p > 1 and ln % p == 0:
            def spread(x):
                parts = x.reshape((p, ln // p) + x.shape[1:])
                return jax.lax.all_to_all(parts, axis_name, 0, 0).reshape(x.shape)

            words = spread(words)
            rids = spread(rids)

        # ---- phase 1: local sort (block bitonic + merge in kernel path;
        # lax.sort here — same comparator structure) -------------------------
        sw, srid = _local_shard_sort(words, rids)

        if p == 1:
            pad = recv - ln
            keys = jnp.concatenate([sw, jnp.full((pad, n_words), _SENTINEL)], axis=0) if pad else sw
            out_r = jnp.concatenate([srid, jnp.full((pad,), _SENTINEL)]) if pad else srid
            valid = jnp.arange(recv) < ln
            return keys[:recv], out_r[:recv], valid, jnp.int32(0)

        # ---- phase 2: regular sampling -> global splitters ------------------
        # Splitters extend the key with the rid: the perfect partition of
        # Francis-Mathieson-Pannan splits runs of EQUAL keys across
        # processors; a (key ++ rid) splitter reproduces that tie handling,
        # so duplicate-heavy inputs (Zipf keys) still balance.
        step = max(ln // p, 1)
        samp_idx = jnp.minimum(jnp.arange(p) * step + step // 2, ln - 1)
        keyed = jnp.concatenate([sw, srid[:, None]], axis=1)  # (ln, W+1)
        samples = keyed[samp_idx]  # (p, W+1)
        all_samples = jax.lax.all_gather(samples, axis_name)  # (p, p, W+1)
        flat = all_samples.reshape(p * p, n_words + 1)
        (sorted_samples,) = sort_words(flat)
        splitters = sorted_samples[jnp.arange(1, p) * p]  # (p-1, W+1)

        # ---- phase 3: bucket assignment (locally sorted => buckets are
        # contiguous runs) ----------------------------------------------------
        # bucket(key) = #splitters <= key, via multiword lexicographic compare
        def le(a, b):  # a (m,W) splitters vs b (ln,W) keys -> (ln, m)
            lt = a[None, :, :] < b[:, None, :]
            eq = a[None, :, :] == b[:, None, :]
            eq_prefix = jnp.cumprod(
                jnp.concatenate(
                    [jnp.ones_like(eq[..., :1], jnp.int32), eq[..., :-1].astype(jnp.int32)],
                    axis=-1,
                ),
                axis=-1,
            ).astype(bool)
            less = jnp.any(lt & eq_prefix, axis=-1)
            equal = jnp.all(eq, axis=-1)
            return less | equal

        bucket = jnp.sum(le(splitters, keyed), axis=1).astype(jnp.int32)  # (ln,)
        start = jnp.searchsorted(bucket, jnp.arange(p), side="left")
        within = jnp.arange(ln, dtype=jnp.int32) - start[bucket]
        overflow = jnp.sum((within >= cap).astype(jnp.int32))

        # ---- phase 4: scatter into per-destination capacity buckets ---------
        send_keys = jnp.full((p, cap, n_words), _SENTINEL, dtype=jnp.uint32)
        send_rids = jnp.full((p, cap), _SENTINEL, dtype=jnp.uint32)
        send_valid = jnp.zeros((p, cap), dtype=jnp.uint32)
        ok = within < cap
        w_idx = jnp.where(ok, within, cap)  # cap is out of bounds -> dropped
        send_keys = send_keys.at[bucket, w_idx].set(sw, mode="drop")
        send_rids = send_rids.at[bucket, w_idx].set(srid, mode="drop")
        send_valid = send_valid.at[bucket, w_idx].set(jnp.uint32(1), mode="drop")

        # ---- phase 5: the "shared memory" step -> ICI all_to_all -------------
        recv_keys = jax.lax.all_to_all(send_keys, axis_name, 0, 0, tiled=False)
        recv_rids = jax.lax.all_to_all(send_rids, axis_name, 0, 0, tiled=False)
        recv_valid = jax.lax.all_to_all(send_valid, axis_name, 0, 0, tiled=False)

        # ---- phase 6: final local merge --------------------------------------
        rk = recv_keys.reshape(recv, n_words)
        rr = recv_rids.reshape(recv)
        rv = recv_valid.reshape(recv)
        # invalid rows carry sentinels already; sort once more (merge of p
        # runs), rid again a key word so equal keys land in (key, rid) order
        mk, mr, mv = sort_words_keyed(rk, rr, rv.astype(jnp.uint32))
        total_overflow = jax.lax.psum(overflow, axis_name)
        return mk, mr, mv.astype(jnp.bool_), total_overflow

    mapped = jax.shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(P(axis_name, None), P(axis_name)),
        out_specs=(P(axis_name, None), P(axis_name), P(axis_name), P()),
    )

    @jax.jit
    def run_arrays(words, rids):
        return mapped(jnp.asarray(words, jnp.uint32), jnp.asarray(rids, jnp.uint32))

    def run(words, rids):
        k, r, v, ov = run_arrays(words, rids)
        return DistSortResult(keys=k, rids=r, valid=v, overflow=ov)

    return run


def sample_sort(
    words: jnp.ndarray,
    rids: jnp.ndarray,
    mesh: Mesh,
    axis_name: str = "data",
    capacity_factor: float = 1.5,
) -> DistSortResult:
    """Convenience wrapper: build + run the distributed sort."""
    n, w = words.shape
    p = mesh.shape[axis_name]
    if n % p:
        raise ValueError(f"n={n} must divide evenly over axis {axis_name}={p}")
    fn = make_sample_sort(mesh, axis_name, n // p, w, capacity_factor)
    return fn(words, rids)
