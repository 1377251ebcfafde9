"""Mesh-distributed backend: the row-column sample sort over one mesh axis.

Wraps ``repro.core.distsort.make_sample_sort`` so a mesh run produces the
same (keys_sorted, rows_sorted) pair — and therefore the same
``ReconstructionResult`` shape — as the single-device backends.

**ICI volume** is the reason this backend exists inside the pipeline rather
than as a bolted-on flag: the pipeline's extract stage runs *before* the
sort stage, i.e. before the sample sort's bucketed ``all_to_all``, so the
bytes crossing the interconnect are the compressed sort keys.  The exchange
volume shrinks by exactly the paper's sort-key ratio — compression does not
merely shorten the comparator, it shrinks the step the paper maps to shared
memory (distsort docstring, "perfect partition -> regular-sampling
splitters + bucketed all_to_all").

Static-shape adaptation (see distsort): buckets carry a capacity factor and
the kernel *reports* overflow instead of dropping silently.  This backend
retries with doubled capacity until the sort is overflow-free and records
the attempts in ``last_info`` — callers see exactly the MoE-dispatch
compromise, never a wrong answer.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.compat import make_mesh
from repro.core.compress import ExtractionPlan, extract_bits
from repro.core.dbits import rank_in_sorted_keyed
from repro.core.distsort import make_sample_sort
from repro.core.plancache import (
    bucket_for,
    get_cache,
    iota_u32,
    merge_padded,
    pad_run,
    pad_tail,
)

from .base import ExecutionBackend, register_backend

__all__ = ["DistributedBackend"]

_SENTINEL = np.uint32(0xFFFFFFFF)


@register_backend("distributed")
class DistributedBackend(ExecutionBackend):
    """shard_map sample sort over ``axis_name`` of ``mesh``."""

    supports_batched = True

    def __init__(
        self,
        mesh=None,
        axis_name: str = "data",
        capacity_factor: float = 1.5,
        max_capacity_retries: int = 4,
    ) -> None:
        super().__init__()
        if mesh is None:
            mesh = make_mesh((len(jax.devices()),), (axis_name,))
        self.mesh = mesh
        self.axis_name = axis_name
        self.capacity_factor = float(capacity_factor)
        self.max_capacity_retries = int(max_capacity_retries)
        self._fns: dict = {}  # (n_per_shard, n_words, capacity) -> sort fn
        self.last_info = {"mesh_devices": int(mesh.shape[axis_name])}

    @property
    def n_devices(self) -> int:
        return int(self.mesh.shape[self.axis_name])

    def extract(self, words: jnp.ndarray, plan: ExtractionPlan) -> jnp.ndarray:
        # Extraction is embarrassingly row-parallel; under the mesh it runs
        # shard-local ahead of the exchange (this ordering is what shrinks
        # the all_to_all byte volume by the sort-key ratio).
        return extract_bits(jnp.asarray(words, jnp.uint32), plan)

    def _sort_fn(self, n_per_shard: int, n_words: int, capacity: float):
        key = (n_per_shard, n_words, capacity)
        if key not in self._fns:
            self._fns[key] = make_sample_sort(
                self.mesh, self.axis_name, n_per_shard, n_words, capacity
            )
        return self._fns[key]

    def sort(self, keys, rows, *, n_valid=None, keep_padded=False, donate=False):
        # ``donate`` is accepted for signature parity but ignored: the
        # sample sort compacts its shard-padded result host-side, so there
        # is no single compiled program whose output could alias the input
        # buffer.  Outputs are identical either way (the flag is a memory
        # hint, never a semantic one).
        del donate
        keys = jnp.asarray(keys, jnp.uint32)
        rows = jnp.asarray(rows, jnp.uint32)
        b, w = (int(s) for s in keys.shape)
        n = b if n_valid is None else int(n_valid)
        p = self.n_devices

        if n_valid is not None:
            # inputs are bucket-shaped with arbitrary pad lanes; normalize
            # from the dynamic count (scalar broadcasts — no materialized
            # per-call fill): pad keys to the sentinel, pad rows to their
            # lane index (>= n, so compaction strips them)
            lane = iota_u32(b)
            valid = lane < jnp.uint32(n)
            keys = jnp.where(valid[:, None], keys, jnp.uint32(_SENTINEL))
            rows = jnp.where(valid, rows, lane)

        # shard padding occupies row ids n..; reject out-of-range rows
        # rather than silently confusing them with padding
        if n and int(jnp.max(rows[:n])) >= n:
            raise ValueError(
                "distributed backend requires row positions in [0, n); "
                f"got max row {int(jnp.max(rows[:n]))} for n={n}"
            )

        # pad to a shard multiple; sentinel keys sort last, pad row ids are
        # n.. so the (key, row) tie-break keeps real all-ones keys ahead.
        # Concat-free: sentinel tail via the cached-constant pad, row tail
        # via one dynamic_update_slice of the real rows into a cached iota
        # (its untouched tail lanes are exactly the pad ids cur_n..total-1)
        cur = int(keys.shape[0])
        total = cur + ((-cur) % p)
        if total != cur:
            keys = pad_tail(keys, total, _SENTINEL)
            import jax.lax as lax

            rows = lax.dynamic_update_slice(iota_u32(total), rows, (0,))

        res = self.sample_sort_raw(keys, rows)

        # compact the shard-padded result to the dense global order
        valid = np.asarray(res.valid)
        k = np.asarray(res.keys)[valid]
        r = np.asarray(res.rids)[valid]
        real = r < n
        k, r = k[real], r[real]
        ks = jnp.asarray(k, jnp.uint32)
        rs = jnp.asarray(r, jnp.uint32)
        if keep_padded:
            return pad_run(ks, rs, b if n_valid is not None else bucket_for("sort", n))
        return ks, rs

    def merge_sorted(self, keys_a, rows_a, keys_b, rows_b, *,
                     n_valid_a=None, n_valid_b=None, keep_padded=False,
                     donate=False):
        """Owner-shard routing + shard-local merges.

        The base run A is globally sorted, i.e. already range-partitioned
        into ``p`` contiguous shard chunks.  Only the (small) delta B moves:
        each element is routed to the chunk that owns its merge position
        (one rank binary search against A), then every chunk merges locally
        with its routed slice — so the bytes crossing the interconnect are
        the delta, never the base.  This is the same economics as the
        extract-before-all_to_all ordering of the sort stage: incremental
        maintenance keeps the bulk data shard-resident.

        ``n_valid_a``/``n_valid_b`` mark bucket-shaped runs (the valid
        prefix merges; pads are dropped before routing); ``keep_padded``
        re-pads the merged run to ``ba + bb`` rows.  ``donate`` is ignored
        — the routing path is host-side, so there is no program whose
        output could alias the inputs (see :meth:`sort`).
        """
        del donate
        keys_a = jnp.asarray(keys_a, jnp.uint32)
        rows_a = jnp.asarray(rows_a, jnp.uint32)
        keys_b = jnp.asarray(keys_b, jnp.uint32)
        rows_b = jnp.asarray(rows_b, jnp.uint32)
        ba, bb = int(keys_a.shape[0]), int(keys_b.shape[0])
        if n_valid_a is not None:
            keys_a, rows_a = keys_a[: int(n_valid_a)], rows_a[: int(n_valid_a)]
        if n_valid_b is not None:
            keys_b, rows_b = keys_b[: int(n_valid_b)], rows_b[: int(n_valid_b)]
        na, nb = int(keys_a.shape[0]), int(keys_b.shape[0])

        def _shape_out(ks, rs):
            if not keep_padded:
                return ks, rs
            return pad_run(ks, rs, ba + bb)

        p = self.n_devices
        if na == 0 or nb == 0 or p == 1:
            mk, mr = merge_padded(keys_a, rows_a, keys_b, rows_b,
                                  backend=self.name)
            self.last_info = {"mesh_devices": p, "delta_routed": [nb]}
            return _shape_out(mk, mr)
        chunk = -(-na // p)
        # rank of each delta element in the base run decides the owner chunk:
        # rank r lands between A[r-1] and A[r], i.e. inside chunk r // chunk
        rank_b = np.asarray(
            rank_in_sorted_keyed(keys_a, rows_a, keys_b, rows_b)
        )
        owner = np.minimum(rank_b // chunk, p - 1)
        parts_k, parts_r, routed = [], [], []
        for i in range(p):
            s, e = i * chunk, min((i + 1) * chunk, na)
            sel = np.nonzero(owner == i)[0]
            routed.append(int(sel.size))
            # chunk sizes drift with (na, routed delta); bucketing the local
            # merge keeps every chunk on a cached compiled program
            mk, mr = merge_padded(
                keys_a[s:e], rows_a[s:e],
                jnp.take(keys_b, sel, axis=0), jnp.take(rows_b, sel, axis=0),
                backend=self.name,
            )
            parts_k.append(mk)
            parts_r.append(mr)
        self.last_info = {"mesh_devices": p, "delta_routed": routed}
        return _shape_out(
            jnp.concatenate(parts_k, axis=0), jnp.concatenate(parts_r, axis=0)
        )

    def lookup(self, tree, queries):
        """Owner-shard routed point lookups.

        The sorted key space is range-partitioned into ``p`` contiguous
        chunks (the same partition the sample sort produced); each query
        is routed to the chunk that owns its key range — one vectorized
        compare against the ``p - 1`` chunk boundary keys — and every
        owner answers its group through the shared plan-cached lookup
        program.  Group sizes drift with the query mix, so the bucketed
        program is what keeps a steady routed stream replay-only; answers
        are scattered back into query order, byte-identical to the
        unrouted oracle because each query's answer is independent of its
        group.  ``last_info["lookup_routed"]`` records the per-shard
        query counts.
        """
        from repro.core.btree import NOT_FOUND_RID, lookup_batch_planned
        from repro.core.dbits import lex_compare_le

        queries = jnp.asarray(queries, jnp.uint32)
        q = int(queries.shape[0])
        p = self.n_devices
        n = int(tree.n_keys)
        if p == 1 or q == 0 or n < p:
            out = lookup_batch_planned(tree, queries, backend_name=self.name)
            self.last_info = {"mesh_devices": p, "lookup_routed": [q]}
            return out
        chunk = -(-n // p)
        # boundary b is the first key of chunk b+1; a query belongs to the
        # last chunk whose boundary is <= it (compare over all boundaries
        # at once — log-free, p is the mesh size)
        bounds = tree.sorted_full[
            jnp.minimum(jnp.arange(1, p, dtype=jnp.int32) * chunk, n - 1)
        ]
        owner = np.asarray(
            jnp.sum(
                lex_compare_le(bounds[None, :, :], queries[:, None, :]).astype(
                    jnp.int32
                ),
                axis=1,
            )
        )
        found = np.zeros((q,), bool)
        rid = np.full((q,), NOT_FOUND_RID, np.uint32)
        routed = []
        for i in range(p):
            sel = np.nonzero(owner == i)[0]
            routed.append(int(sel.size))
            if not sel.size:
                continue
            f, r = lookup_batch_planned(
                tree, jnp.take(queries, sel, axis=0), backend_name=self.name
            )
            found[sel] = np.asarray(f)
            rid[sel] = np.asarray(r)
        self.last_info = {"mesh_devices": p, "lookup_routed": routed}
        return jnp.asarray(found), jnp.asarray(rid, jnp.uint32)

    def lookup_many(self, stacked, queries, n_valid=None):
        """Fused multi-tenant lookup with the tenant axis over the mesh.

        The stacked arena is the natural distribution unit: every tenant's
        descent is independent, so the whole BTree pytree shards on its
        leading tenant axis (``T / p`` tenants per device) and the fused
        body runs shard-locally under ``shard_map`` — batch parallelism
        with zero interconnect bytes, the read-path twin of
        :meth:`batched_extract_sort`.  The shard_mapped program is
        memoized per ``(T, query bucket, geometry, p)``; falls back to the
        single-device fused path when the arena does not tile the mesh
        axis.  ``last_info["tenants_per_shard"]`` records the placement.
        """
        from repro.core.btree import (
            _leaf_match_many_full,
            _lookup_many_body,
            lookup_many_planned,
            tree_geometry,
        )

        queries = jnp.asarray(queries, jnp.uint32)
        t_q, q, w = (int(s) for s in queries.shape)
        t_cap = int(stacked.sorted_full.shape[0])
        p = self.n_devices
        if p == 1 or t_cap % p:
            self.last_info = {"mesh_devices": p, "tenants_per_shard": t_cap}
            return lookup_many_planned(
                stacked, queries, n_valid, backend_name=self.name
            )
        if t_q > t_cap:
            raise ValueError(f"{t_q} tenant blocks > arena capacity {t_cap}")

        from jax.sharding import PartitionSpec as P

        cache = get_cache()
        b = bucket_for("lookup_many", q)
        if n_valid is None:
            nv = np.full((t_q,), q, np.uint32)
        else:
            nv = np.asarray(n_valid, np.uint32).reshape(-1)
            if nv.shape[0] != t_q:
                raise ValueError(
                    f"n_valid has {nv.shape[0]} rows, expected {t_q}"
                )
        nv_full = np.zeros((t_cap,), np.uint32)
        nv_full[:t_q] = np.minimum(nv, q)

        def builder():
            body = _lookup_many_body(_leaf_match_many_full)
            spec = P(self.axis_name)  # shard the leading (tenant) axis
            fn = jax.shard_map(
                body,
                mesh=self.mesh,
                in_specs=(spec, spec, spec),
                out_specs=(spec, spec),
            )
            return cache.jit(fn)

        prog = cache.program(
            ("lookup_many", self.name, t_cap, b, w, tree_geometry(stacked), p),
            builder,
        )
        qp = pad_tail(queries, b, 0xFFFFFFFF, axis=1)
        qp = pad_tail(qp, t_cap, 0xFFFFFFFF, axis=0)
        found, rid = prog(stacked, qp, jnp.asarray(nv_full))
        self.last_info = {"mesh_devices": p, "tenants_per_shard": t_cap // p}
        return found[:t_q, :q], rid[:t_q, :q]

    def batched_extract_sort(self, words, bitmaps, rows, plans):
        """Shards ``run_many``'s *batch* axis across the mesh.

        Each device extracts + sorts its ``k / p`` keysets entirely
        shard-locally — batch parallelism instead of the sample sort's key
        parallelism, so no bytes cross the interconnect at all.  The
        shard_mapped program is memoized in the shared plan cache per
        ``(k, n, W, Wc, p)``, so replication batches replay it.  Falls
        back to the single-device vmap when the batch does not tile the
        mesh axis.
        """
        k = int(words.shape[0])
        p = self.n_devices
        if p == 1 or k % p:
            return super().batched_extract_sort(words, bitmaps, rows, plans)

        from jax.sharding import PartitionSpec as P

        cache = get_cache()
        _, n, w = (int(s) for s in words.shape)
        n_words_out = plans[0].n_words_out  # equal across the batch

        def builder():
            from repro.core.compress import extract_bits_dynamic
            from repro.core.dbits import sort_words_keyed

            def one(wds, bm, r):
                comp = extract_bits_dynamic(wds, bm, n_words_out)
                return sort_words_keyed(comp, r)

            local = jax.vmap(one, in_axes=(0, 0, 0))
            spec3 = P(self.axis_name, None, None)
            spec2 = P(self.axis_name, None)
            fn = jax.shard_map(
                local,
                mesh=self.mesh,
                in_specs=(spec3, spec2, spec2),
                out_specs=(spec3, spec2),
            )
            return cache.jit(fn)

        prog = cache.program(
            ("run_many", self.name, k, n, w, n_words_out, p), builder
        )
        self.last_info = {"mesh_devices": p, "batch_per_shard": k // p}
        return prog(
            jnp.asarray(words, jnp.uint32),
            jnp.asarray(bitmaps, jnp.uint32),
            jnp.asarray(rows, jnp.uint32),
        )

    def sample_sort_raw(self, keys, rows):
        """Device-side sample sort with overflow retry: the shard-padded
        ``DistSortResult`` (keys/rids/valid stay device arrays; no host
        compaction).  For callers that time or post-process on device —
        the scaling benchmarks use this so host traffic is not measured.
        ``n`` must already be a multiple of the mesh axis size (``sort``
        handles padding)."""
        keys = jnp.asarray(keys, jnp.uint32)
        rows = jnp.asarray(rows, jnp.uint32)
        n, w = keys.shape
        p = self.n_devices
        if n % p:
            raise ValueError(f"n={n} must divide over {p} devices")
        capacity = self.capacity_factor
        attempts = 0
        while True:
            attempts += 1
            fn = self._sort_fn(n // p, w, capacity)
            res = fn(keys, rows)
            overflow = int(res.overflow)
            if overflow == 0:
                break
            if attempts > self.max_capacity_retries:
                raise RuntimeError(
                    f"distributed sort still overflowing after "
                    f"{attempts} attempts (capacity {capacity}, "
                    f"overflow {overflow})"
                )
            capacity *= 2.0
        self.last_info = {
            "mesh_devices": p,
            "capacity_factor": capacity,
            "capacity_retries": attempts - 1,
            "overflow": overflow,
        }
        return res
