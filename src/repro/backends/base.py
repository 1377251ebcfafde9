"""Execution-backend interface + registry for the reconstruction pipeline.

A backend supplies the two data-parallel stages of the paper's pipeline —
compressed-key **extract** (§5.1) and parallel **sort** (§5.2) — behind one
interface, so ``repro.core.pipeline`` can run the identical scan → extract →
sort → build → refresh flow on the pure-jnp oracle path, the Pallas kernels,
or a mesh-distributed sample sort without any call-site branching (the
encoder/executor split HOPE and Upscaledb argue for).

Determinism contract: ``sort`` orders rows by the lexicographic pair
``(key, row)`` — ties between equal keys break on the ascending row id.
Every backend honours it, which is what makes the sorted compressed keys and
rid permutations *byte-identical* across backends (and what the parity tests
assert).  All three built-in backends realize it the same way: the row id is
carried as an extra least-significant sort-key word (the paper's sort key is
literally the (compressed key, rid) pair).  Rows are the pipeline's row
*positions* — distinct values in ``[0, n)``; the distributed backend
validates this because its shard padding occupies ids ``>= n``.

The incremental reconstruction path adds a third data-parallel op,
``merge_sorted``: given two runs that are each ascending in (key, row) —
the surviving base run and the freshly sorted delta — produce the merged
run.  The contract extends naturally: the output must be byte-identical to
``sort`` over the concatenated pairs (rows must be distinct *across* the two
runs, so the (key, row) order is total).  Backends realize it differently —
merge-path ranks on jnp, the tiled rank kernel on pallas, owner-shard
routing + local merges on the distributed mesh — but the output bytes are
the same everywhere.

Compiled-plan execution promotes the remaining serial stages to backend
ops: ``build`` (§5.3 bulk build — per-level entry programs cached in the
shared plan cache, with a backend-substitutable partial-key gather) and
``refresh_meta`` (§4.3 — a cached device program that OR-reduces the
adjacent D-bits into the compressed-space bitmap, mapped to full-key bits
on the host).  Shape-polymorphic ops (sort, merge, fused extract+sort,
the batched path) run through
``repro.core.plancache``: inputs pad to power-of-two buckets and the
compiled program is memoized per ``(op, backend, bucket, n_words,
static config)``, so drifting sizes under a churny serving load replay
cached programs instead of retracing.

The read path adds a fourth data-parallel family, ``lookup``: batched
point lookups against a built tree, plan-cached per query-batch bucket.
The contract is byte-identity again — ``(found, rid)`` with miss lanes
normalized to ``repro.core.btree.NOT_FOUND_RID`` must be bit-for-bit
equal across backends (jnp full-key descent, the pallas partial-key
probe kernel, distributed owner-shard routing), which is what lets a
reader switch substrates — or snapshot epochs built on different
substrates — without ever seeing a divergent answer.
"""

from __future__ import annotations

import abc
from typing import TYPE_CHECKING, Callable, Type

import jax.numpy as jnp

if TYPE_CHECKING:  # real import stays lazy: repro.core.__init__ imports the
    # pipeline, which imports this package — a module-level import here
    # would close that cycle before the registry names exist
    from repro.core.compress import ExtractionPlan

__all__ = [
    "ExecutionBackend",
    "register_backend",
    "get_backend",
    "available_backends",
]

_REGISTRY: dict[str, Type["ExecutionBackend"]] = {}


def register_backend(name: str) -> Callable[[type], type]:
    """Class decorator: register an ExecutionBackend under ``name``."""

    def deco(cls: type) -> type:
        cls.name = name
        _REGISTRY[name] = cls
        return cls

    return deco


def get_backend(name: str, **opts) -> "ExecutionBackend":
    """Instantiate a registered backend (options are backend-specific)."""
    if name not in _REGISTRY:
        raise KeyError(
            f"unknown backend {name!r}; registered: {sorted(_REGISTRY)}"
        )
    return _REGISTRY[name](**opts)


def available_backends() -> list[str]:
    """Sorted names of every registered execution backend."""
    return sorted(_REGISTRY)


class ExecutionBackend(abc.ABC):
    """One execution substrate for the pipeline's extract and sort stages.

    ``last_info`` holds backend-specific facts about the most recent sort
    (e.g. distsort overflow retries); the pipeline folds it into
    ``ReconstructionResult.stats``.
    """

    name: str = "?"
    #: backend can run extract+sort as one fused program (the compressed
    #: array is never materialized between the stages)
    supports_fused: bool = False
    #: backend's extract+sort can be vmapped over a stacked batch of
    #: same-shape keysets (single-device jnp semantics; the pipeline's
    #: run_many uses this for the batched fast path)
    supports_batched: bool = False

    def __init__(self) -> None:
        self.last_info: dict = {}

    # ------------------------------------------------------------ extract
    @abc.abstractmethod
    def extract(self, words: jnp.ndarray, plan: "ExtractionPlan") -> jnp.ndarray:
        """(n, W) uint32 full keys -> (n, Wc) uint32 compressed keys."""

    def extract_dynamic(
        self, words: jnp.ndarray, bitmap: jnp.ndarray, n_words_out: int
    ) -> jnp.ndarray:
        """Runtime-bitmap extraction (no per-bitmap retrace); jnp fallback."""
        from repro.core.compress import extract_bits_dynamic

        return extract_bits_dynamic(words, bitmap, n_words_out)

    # --------------------------------------------------------------- sort
    @abc.abstractmethod
    def sort(
        self,
        keys: jnp.ndarray,
        rows: jnp.ndarray,
        *,
        n_valid: int | None = None,
        keep_padded: bool = False,
        donate: bool = False,
    ) -> tuple[jnp.ndarray, jnp.ndarray]:
        """Sort (n, W) keys with (n,) distinct row positions in [0, n).

        Returns (keys_sorted, rows_sorted) in ascending (key, row) order —
        see the determinism contract in the module docstring.

        ``n_valid`` marks the inputs as already bucket-shaped with
        ``n_valid`` real rows (pad lanes may be arbitrary; the cached
        program normalizes them from the dynamic count).  ``keep_padded``
        returns the bucket-shaped outputs (pads sorted to the tail) so
        the pipeline can chain into the build programs without slicing
        and re-padding.

        ``donate=True`` is the caller's assertion that nothing else reads
        the *keys* buffer again — the compiled program consumes it
        (``donate_argnums``) and XLA reuses its storage.  The rows operand
        is never donated (it is often the shared cached iota).  Backends
        without compiled-program donation (the distributed host-routing
        path) may ignore the flag; outputs are identical either way.
        """

    # -------------------------------------------------------- fused path
    def fused_extract_sort(
        self,
        words: jnp.ndarray,
        plan: ExtractionPlan,
        rows: jnp.ndarray,
        *,
        n_valid: int | None = None,
        keep_padded: bool = False,
        donate: bool = False,
    ) -> tuple[jnp.ndarray, jnp.ndarray]:
        """extract+sort as one program; only if ``supports_fused``.

        ``n_valid`` / ``keep_padded`` / ``donate`` behave as in
        :meth:`sort` (``donate`` consumes the words operand).
        """
        raise NotImplementedError(f"backend {self.name} has no fused path")

    # -------------------------------------------------------------- merge
    def merge_sorted(
        self,
        keys_a: jnp.ndarray,
        rows_a: jnp.ndarray,
        keys_b: jnp.ndarray,
        rows_b: jnp.ndarray,
        *,
        n_valid_a: int | None = None,
        n_valid_b: int | None = None,
        keep_padded: bool = False,
        donate: bool = False,
    ) -> tuple[jnp.ndarray, jnp.ndarray]:
        """Merge two ascending (key, row) runs into one.

        Must be byte-identical to ``sort`` over the concatenated inputs;
        rows must be distinct across both runs (see the module docstring).
        The default is the jnp merge-path reference, shape-bucketed so
        drifting ``(na, nb)`` pairs inside a bucket replay one compiled
        program; backends override with their native realization.

        ``n_valid_a``/``n_valid_b`` mark the runs as bucket-shaped with
        that many valid rows; ``keep_padded`` returns the full
        ``(ba + bb,)`` outputs with pads at the tail (cascade chaining).
        ``donate=True`` consumes all four run operands — a merge's inputs
        are dead after it, so the cascade's peak live footprint stays
        O(log) runs.  Backends that merge host-side may ignore ``donate``.
        """
        from repro.core.plancache import merge_padded

        return merge_padded(
            jnp.asarray(keys_a, jnp.uint32), jnp.asarray(rows_a, jnp.uint32),
            jnp.asarray(keys_b, jnp.uint32), jnp.asarray(rows_b, jnp.uint32),
            backend=self.name, n_valid_a=n_valid_a, n_valid_b=n_valid_b,
            keep_padded=keep_padded, donate=donate,
        )

    # -------------------------------------------------------------- build
    def build(
        self,
        comp_sorted: jnp.ndarray,
        row_sorted: jnp.ndarray,
        meta,
        words: jnp.ndarray,
        lengths: jnp.ndarray | None,
        config,
        rids: jnp.ndarray | None = None,
        n_valid: int | None = None,
        donate: bool = False,
    ):
        """Stage 3 (§5.3): bottom-up bulk build of the partial-key B+tree.

        The default runs the cached jnp build programs; backends may
        substitute their own entry-gather realization (the Pallas backend
        passes its ``kernels/build`` pk-window kernel) — output trees must
        be byte-identical across backends.  ``n_valid`` marks
        ``comp_sorted``/``row_sorted`` as bucket-shaped with ``n_valid``
        real rows (the pipeline chains the sort stage's padded outputs in
        without re-padding).  ``donate=True`` lets the build programs
        consume their scratch operands (the sort permutation and the
        per-level hi-index buffer) — only safe when the caller no longer
        reads the padded row buffer afterwards.
        """
        from repro.core.btree import build_btree

        return build_btree(
            comp_sorted, row_sorted, meta, words, lengths, config,
            rids=rids, backend_name=self.name, n_valid=n_valid,
            donate=donate,
        )

    # ------------------------------------------------------------- lookup
    def lookup(
        self, tree, queries: jnp.ndarray
    ) -> tuple[jnp.ndarray, jnp.ndarray]:
        """Batched point lookup: (q, W) queries -> ((q,) found, (q,) rid).

        Miss lanes carry ``repro.core.btree.NOT_FOUND_RID``; outputs must
        be byte-identical across backends (the read-path analogue of the
        sort contract).  The default is the plan-cached full-key descent —
        one compiled program per query-batch bucket, so a steady query
        stream at drifting batch sizes replays without retracing.
        Backends substitute their own leaf probe (the pallas partial-key
        kernel) or routing (the distributed owner shards).
        """
        from repro.core.btree import lookup_batch_planned

        return lookup_batch_planned(
            tree, jnp.asarray(queries, jnp.uint32), backend_name=self.name
        )

    # ------------------------------------------------- lookup (multi-tenant)
    def lookup_many(
        self, stacked, queries: jnp.ndarray, n_valid=None
    ) -> tuple[jnp.ndarray, jnp.ndarray]:
        """Fused point lookup over T stacked same-geometry trees.

        ``stacked`` is a ``repro.core.btree.stack_trees`` arena;
        ``queries`` is (T_q, q, W) with ``T_q`` at most the arena
        capacity, tenant ``t``'s block answered against member tree
        ``t``; ``n_valid`` (optional (T_q,)) gives per-tenant live lane
        counts.  Returns ``((T_q, q) found, (T_q, q) rid)`` — each
        tenant's slice byte-identical to :meth:`lookup` on that tenant's
        tree alone, which is the single-snapshot contract lifted over
        the tenant axis.  The default is the jnp oracle: ``vmap`` of the
        plan-cached descent over the tenant axis, one compiled program
        per ``(T, query bucket, tree geometry)``.  Backends substitute
        their own realization (the pallas probe kernel's tenant-major
        grid; distributed sharding of the tenant axis over the mesh).
        """
        from repro.core.btree import lookup_many_planned

        return lookup_many_planned(
            stacked, jnp.asarray(queries, jnp.uint32), n_valid,
            backend_name=self.name,
        )

    # ------------------------------------------------------- refresh meta
    def refresh_meta(self, comp_sorted: jnp.ndarray, meta, ref_key,
                     n_valid: int | None = None, donate: bool = False):
        """Stage 4 (§4.3): recompute DS-metadata at the opportune time.

        A cached, shape-bucketed device program OR-reduces the adjacent
        D-bits of ``comp_sorted`` into the compressed-space D-bitmap;
        only those Wc words cross to the host, where ``meta_on_rebuild``
        maps each set bit through D-offset.  ``n_valid`` marks
        ``comp_sorted`` as bucket-shaped with ``n_valid`` real rows.
        ``donate=True`` consumes ``comp_sorted`` — refresh is the
        pipeline's last consumer of the padded sorted run, so its buffer
        is reclaimed in place; only pass it when nothing else reads that
        buffer again.
        """
        import numpy as np

        from repro.core.metadata import meta_on_rebuild
        from repro.core.plancache import adjacent_dbitmap_padded

        comp_dbitmap = adjacent_dbitmap_padded(
            jnp.asarray(comp_sorted, jnp.uint32), backend=self.name,
            n_valid=n_valid, donate=donate,
        )
        return meta_on_rebuild(
            None, meta, np.asarray(ref_key), comp_dbitmap=comp_dbitmap
        )

    # ----------------------------------------------------- batched (many)
    def batched_extract_sort(
        self,
        words: jnp.ndarray,
        bitmaps: jnp.ndarray,
        rows: jnp.ndarray,
        plans: list["ExtractionPlan"],
    ) -> tuple[jnp.ndarray, jnp.ndarray]:
        """Extract+sort a stacked batch of same-shape keysets.

        ``words``: (k, n, W); ``bitmaps``: (k, W) per-index D-bitmaps (all
        with the same output width); ``rows``: (k, n); ``plans``: the static
        per-index extraction plans (for backends whose extractor wants a
        trace-time schedule).  Returns (comp_sorted (k, n, Wc), row_sorted
        (k, n)).  Only called when ``supports_batched``; the default is the
        vmapped dynamic-bitmap extract + keyed sort (single-device jnp
        semantics), compiled once per ``(k, n, W, Wc)`` via the plan cache
        (the pipeline pads the stacked ``n`` up to a bucket boundary, so
        replication batches at drifting sizes replay the same program).
        """
        import jax

        from repro.core.plancache import get_cache

        cache = get_cache()
        k, n, w = (int(s) for s in words.shape)
        n_words_out = plans[0].n_words_out  # equal across the batch

        def builder():
            from repro.core.compress import extract_bits_dynamic
            from repro.core.dbits import sort_words_keyed

            def one(wds, bm, r):
                comp = extract_bits_dynamic(wds, bm, n_words_out)
                return sort_words_keyed(comp, r)

            return cache.jit(jax.vmap(one, in_axes=(0, 0, 0)))

        prog = cache.program(
            ("run_many", self.name, k, n, w, n_words_out), builder
        )
        return prog(words, bitmaps, rows)
