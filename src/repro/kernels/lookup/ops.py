"""Jit-friendly public wrappers for the partial-key probe kernel."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.plancache import pad_tail

from .kernel import DEFAULT_TILE, probe_planes, probe_planes_many


def probe(
    queries: jnp.ndarray,
    starts: jnp.ndarray,
    entry_pk: jnp.ndarray,
    pk: int,
    *,
    interpret: bool,
    tile: int = DEFAULT_TILE,
) -> jnp.ndarray:
    """(m, W) query keys + (m,) window starts + (m,) stored partial keys
    -> (m,) bool candidate mask (query window == stored partial key).

    Pads the pair axis to a tile multiple via ``plancache.pad_tail`` (pad
    starts/pks are 0 — garbage lanes, stripped before return; cached zero
    constants, no per-call concatenate), transposes to word planes, and
    runs the tiled kernel.  Traces inside the cached lookup program,
    exactly like ``kernels/build``'s ``slice_fn`` does inside the build
    programs.
    """
    m, w = queries.shape
    total = m + ((-m) % tile)
    planes = pad_tail(jnp.asarray(queries, jnp.uint32).T, total, 0, axis=1)
    starts = pad_tail(jnp.asarray(starts, jnp.int32), total, 0)
    entry_pk = pad_tail(jnp.asarray(entry_pk, jnp.uint32), total, 0)
    out = probe_planes(planes, starts, entry_pk, int(pk), tile=tile, interpret=interpret)
    return out[:m].astype(bool)


def leaf_match_fn(*, interpret: bool, tile: int = DEFAULT_TILE):
    """A ``lookup_batch_planned(leaf_match_fn=...)``-shaped closure.

    Screens every (query, leaf entry) pair with the probe kernel, then
    confirms candidates with the full-key compare — byte-identical to the
    unscreened compare (a full match always window-matches), which is the
    pallas ``lookup`` op's realization of the backend contract.
    """

    def fn(tree, node, keys, queries):
        q, lc = node.shape[0], tree.config.leaf_cap
        dpos = tree.leaf["dpos"][node]  # (q, lc)
        entry_pk = tree.leaf["pk"][node]  # (q, lc)
        flat_q = jnp.repeat(queries, lc, axis=0)  # (q*lc, W) pair queries
        cand = probe(
            flat_q,
            (dpos + 1).reshape(-1),
            entry_pk.reshape(-1),
            tree.config.pk_bits,
            tile=tile,
            interpret=interpret,
        ).reshape(q, lc)
        return cand & jnp.all(keys == queries[:, None, :], axis=-1)

    return fn


def probe_many(
    queries: jnp.ndarray,
    starts: jnp.ndarray,
    entry_pk: jnp.ndarray,
    pk: int,
    *,
    interpret: bool,
    tile: int = DEFAULT_TILE,
) -> jnp.ndarray:
    """(T, m, W) stacked pair queries + (T, m) starts/partial keys
    -> (T, m) bool candidate mask — the tenant-major twin of :func:`probe`.

    Pads the pair axis to a tile multiple against cached constants (pad
    lanes are garbage, stripped before return), transposes each tenant's
    block to word planes, and runs the tenant-major grid kernel — one
    ``pallas_call`` for the whole arena.
    """
    t, m, w = queries.shape
    total = m + ((-m) % tile)
    planes = pad_tail(
        jnp.swapaxes(jnp.asarray(queries, jnp.uint32), 1, 2), total, 0, axis=2
    )
    starts = pad_tail(jnp.asarray(starts, jnp.int32), total, 0, axis=1)
    entry_pk = pad_tail(jnp.asarray(entry_pk, jnp.uint32), total, 0, axis=1)
    out = probe_planes_many(
        planes, starts, entry_pk, int(pk), tile=tile, interpret=interpret
    )
    return out[:, :m].astype(bool)


def leaf_match_many_fn(*, interpret: bool, tile: int = DEFAULT_TILE):
    """A ``lookup_many_planned(leaf_match_many_fn=...)``-shaped closure.

    The stacked twin of :func:`leaf_match_fn`: per-tenant gathers of the
    leaf entries' window starts and stored partial keys, one tenant-major
    probe kernel over every (tenant, query, entry) pair, then the
    full-key confirm — byte-identical per tenant to the single-snapshot
    pallas lookup (a full match always window-matches).
    """

    def fn(tree, node, keys, queries):
        t, q = node.shape
        lc = tree.config.leaf_cap
        gather = jax.vmap(lambda arr, n: arr[n])
        dpos = gather(tree.leaf["dpos"], node)  # (T, q, lc)
        entry_pk = gather(tree.leaf["pk"], node)  # (T, q, lc)
        flat_q = jnp.repeat(queries, lc, axis=1)  # (T, q*lc, W)
        cand = probe_many(
            flat_q,
            (dpos + 1).reshape(t, -1),
            entry_pk.reshape(t, -1),
            tree.config.pk_bits,
            tile=tile,
            interpret=interpret,
        ).reshape(t, q, lc)
        return cand & jnp.all(keys == queries[:, :, None, :], axis=-1)

    return fn
