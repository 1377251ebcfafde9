"""Unified reconstruction pipeline (paper §5, Figure 7) over pluggable backends.

    table (memory-resident) --scan--> extract compressed keys + rids
        --parallel sort--> sorted (comp key, rid) pairs
        --bottom-up build--> partial-key B+tree
        (+ recompute DS-metadata for next time, §4.3)

One pipeline, four explicit stages — ``extract``, ``sort``, ``build``,
``refresh_meta`` — with per-stage wall timings (the paper's Figure 9
breakdown) and per-run stats.  The two data-parallel stages dispatch to an
``ExecutionBackend`` (``repro.backends``): ``jnp`` (oracle), ``pallas``
(PEXT + bitonic kernels), ``distributed`` (mesh sample sort — extraction
runs before the all_to_all, so the ICI byte volume shrinks by the sort-key
ratio).  Every reconstruction call site in the repo — core, serving pager,
checkpoint restore, examples, benchmarks — routes through this class;
backends compose with all of them by construction.

Extras over the plain flow:

* **fused fast path** — when the backend supports it, extract+sort run as
  one program and the compressed array is never materialized between the
  stages (``fused=True``).
* **batched multi-index reconstruction** — ``run_many`` rebuilds many
  independent indexes (the replication scenario of §6): same-shape key sets
  on a backend with ``supports_batched`` are stacked and their extract+sort
  is one batched program (vmapped dynamic-bitmap extraction on jnp, vmapped
  kernels on pallas); tree builds then loop (host-side assembly).
* **incremental delta-merge reconstruction** — ``run_incremental`` folds a
  small change set (deletions as a keep-mask, insertions as a delta keyset)
  into a previous reconstruction *without* re-sorting the base: filter the
  surviving base run, extract+sort only the delta, ``merge_sorted`` the two
  runs on the backend, and rebuild the tree bottom-up from the merged run.
  Output is byte-identical to a full ``run`` over the folded keyset with the
  same DS-metadata; when the D-bitmap changed since the previous extraction
  (the compressed projection moved), it falls back to the full path.
* **snapshot publication** — ``run``/``run_incremental`` *produce*; they
  never mutate a reader-visible index in place.  Passing
  ``publish_to=<repro.core.snapshot.SnapshotCell>`` freezes the finished
  result into an immutable, epoch-stamped ``IndexSnapshot`` and atomically
  swaps it in as the cell's next epoch — readers pinned on the previous
  epoch keep their answers until they release (double buffering); see
  ``repro.core.snapshot``.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field, replace as _dc_replace

import jax
import jax.numpy as jnp
import numpy as np

from repro.backends import ExecutionBackend, get_backend

from .btree import BTree, BTreeConfig
from .keyformat import KeySet
from .metadata import DSMeta, meta_from_keys
from .sortkeys import word_comparison_counts
from .spans import span, spanned

__all__ = [
    "ReconstructionResult",
    "ReconstructionPipeline",
    "identity_meta",
    "fold_keyset",
]


@dataclass
class ReconstructionResult:
    """What a reconstruction returns: the tree, refreshed DS-metadata, the
    sorted compressed keys + rid permutation, and per-stage timings/stats.

    ``extract_bitmap`` is the D-bitmap the compressed keys were *actually*
    extracted under (the input metadata's bitmap — ``meta`` holds the
    refreshed bitmap, which may have shed bits).  ``run_incremental`` merges
    against ``comp_sorted`` only when the current bitmap still equals it.
    """

    tree: BTree
    meta: DSMeta
    comp_sorted: jnp.ndarray
    rid_sorted: jnp.ndarray
    timings: dict = field(default_factory=dict)
    stats: dict = field(default_factory=dict)
    row_sorted: jnp.ndarray | None = None
    extract_bitmap: np.ndarray | None = None
    #: LSN watermark this result is current through (replication consumers
    #: stamp it via ``run``/``run_incremental``; ``None`` = not log-driven)
    watermark: int | None = None


def identity_meta(keyset: KeySet) -> DSMeta:
    """All-ones metadata: every bit position is a distinction bit — the
    full-key baseline (Figure 1 top flow) expressed as a degenerate plan."""
    return DSMeta(
        dbitmap=np.full((keyset.n_words,), 0xFFFFFFFF, np.uint32),
        varbitmap=np.full((keyset.n_words,), 0xFFFFFFFF, np.uint32),
        refkey=np.asarray(keyset.words[0], np.uint32),
        n_words=keyset.n_words,
    )


def fold_keyset(
    base: KeySet,
    keep_rows: np.ndarray | None = None,
    delta: KeySet | None = None,
) -> KeySet:
    """The folded table: surviving base rows, then delta rows appended.

    One boolean mask + one concatenate per column — the vectorized fold
    every incremental call site shares (no per-row Python tuple loop).
    ``keep_rows`` is a (base.n,) bool mask over base *row positions*;
    ``delta`` rows keep their own rids.
    """
    words = np.asarray(base.words, np.uint32)
    lengths = np.asarray(base.lengths, np.int32)
    rids = np.asarray(base.rids, np.uint32)
    if keep_rows is not None:
        keep = np.asarray(keep_rows, bool)
        if keep.shape != (base.n,):
            raise ValueError(f"keep_rows must be ({base.n},), got {keep.shape}")
        words, lengths, rids = words[keep], lengths[keep], rids[keep]
    if delta is not None and delta.n:
        words = np.concatenate([words, np.asarray(delta.words, np.uint32)], axis=0)
        lengths = np.concatenate([lengths, np.asarray(delta.lengths, np.int32)])
        rids = np.concatenate([rids, np.asarray(delta.rids, np.uint32)])
    if words.shape[0] == 0:
        raise ValueError("folded keyset is empty (all rows deleted, no delta)")
    return KeySet(words=words, lengths=lengths, rids=rids)


def _timed(fn, *args):
    t0 = time.perf_counter()
    out = fn(*args)
    out = jax.tree_util.tree_map(
        lambda x: x.block_until_ready() if hasattr(x, "block_until_ready") else x,
        out,
    )
    return out, time.perf_counter() - t0


class ReconstructionPipeline:
    """The scan → extract → sort → build → refresh flow, backend-dispatched.

    Parameters
    ----------
    backend:       a registered backend name (``"jnp"``, ``"pallas"``,
                   ``"distributed"``) or an ``ExecutionBackend`` instance.
    config:        B-tree geometry.
    fused:         run extract+sort as one program when the backend supports
                   it (extract time then reports 0 and folds into sort).
    backend_opts:  forwarded to the backend constructor when ``backend`` is
                   a name (e.g. ``{"interpret": False}`` for pallas on TPU,
                   ``{"mesh": mesh, "capacity_factor": 2.0}`` for distributed).
    chunk_threshold: ``None`` (the default) sorts a rebuild's whole padded
                   bucket in one cached backend ``sort`` program.  An
                   integer instead takes the chunked path for every key
                   count above it: the keyset splits into ``chunk_size``
                   chunks, each sorted through the cached sort program and
                   folded with a binary-counter ladder of cached merges.
    chunk_size:    chunk length for an explicit ``chunk_threshold`` (power
                   of two).
    async_dispatch: skip the per-stage ``block_until_ready`` barriers and
                   sync once at the end of ``run``/``run_incremental``.
                   JAX async dispatch then overlaps host-side program
                   dispatch (chunk i+1's sort) with device compute
                   (chunk i's merge).  Per-stage timings become dispatch
                   walls; pass ``stage_timings=True`` to a run when the
                   Figure-9 breakdown is explicitly wanted (it restores
                   the barriers for that call).  Results are bit-identical
                   either way — only the sync points move.
    donate:        mark operands the stages consume as donated
                   (``donate_argnums``): chunk sorts donate their key
                   slice, the cascade's merges both input runs,
                   build/refresh their scratch.  XLA then reuses a
                   donated buffer in place wherever its shape matches an
                   output (the bucket-shaped sort is the big win — a
                   full zero-copy in-place sort per chunk); operands
                   that can't alias are freed when their Python
                   reference drops, which the ladder does as soon as
                   each run is merged.  No-op on platforms without
                   donation support.
    auto_tune_chunks: lazily calibrate ``chunk_size``/``chunk_threshold``
                   from measured per-bucket sort and merge program costs
                   (:func:`repro.core.plancache.tune_chunking`) the first
                   time a run crosses an explicit threshold (with
                   ``chunk_threshold=None`` no run chunks); the measured
                   :class:`~repro.core.plancache.ChunkPlan` persists on
                   the pipeline and is surfaced in ``stats``.
    """

    def __init__(
        self,
        backend: str | ExecutionBackend = "jnp",
        config: BTreeConfig = BTreeConfig(),
        fused: bool = False,
        backend_opts: dict | None = None,
        chunk_threshold: int | None = None,
        chunk_size: int = 1 << 17,
        async_dispatch: bool = False,
        donate: bool = False,
        auto_tune_chunks: bool = False,
    ) -> None:
        if isinstance(backend, ExecutionBackend):
            self.backend = backend
        else:
            self.backend = get_backend(backend, **(backend_opts or {}))
        self.config = config
        self.fused = bool(fused)
        self.chunk_threshold = (None if chunk_threshold is None
                                else int(chunk_threshold))
        self.chunk_size = int(chunk_size)
        self.async_dispatch = bool(async_dispatch)
        self.donate = bool(donate)
        self.auto_tune_chunks = bool(auto_tune_chunks)
        self.chunk_plan = None
        self._last_cascade: dict = {}
        if self.chunk_size & (self.chunk_size - 1):
            raise ValueError(f"chunk_size must be a power of two, got {chunk_size}")

    # ------------------------------------------------------------- stages
    def extract(self, words: jnp.ndarray, plan) -> jnp.ndarray:
        """Stage 1 (§5.1): full keys -> compressed keys via the D-bitmap."""
        return self.backend.extract(words, plan)

    def sort(self, comp: jnp.ndarray, rows: jnp.ndarray, *,
             n_valid: int | None = None, keep_padded: bool = False,
             donate: bool = False):
        """Stage 2 (§5.2): parallel sort of (comp key, row) pairs."""
        return self.backend.sort(
            comp, rows, n_valid=n_valid, keep_padded=keep_padded, donate=donate
        )

    def build(self, comp_sorted, row_sorted, meta, words, lengths, rids,
              n_valid: int | None = None, donate: bool = False) -> BTree:
        """Stage 3 (§5.3): bottom-up bulk build (backend-dispatched — the
        cached per-level build programs, with backend entry gathers)."""
        return self.backend.build(
            comp_sorted, row_sorted, meta, words, lengths, self.config,
            rids=rids, n_valid=n_valid, donate=donate,
        )

    def refresh_meta(self, comp_sorted, meta: DSMeta, ref_key,
                     n_valid: int | None = None, donate: bool = False) -> DSMeta:
        """Stage 4 (§4.3): recompute DS-metadata at the opportune time
        (backend-dispatched: a cached device program OR-reduces the adjacent
        D-bits into Wc words; the host maps them through D-offset)."""
        return self.backend.refresh_meta(comp_sorted, meta, ref_key,
                                         n_valid=n_valid, donate=donate)

    def tune_chunking(self, **kwargs):
        """Measure this backend's per-bucket sort/merge program costs and
        adopt the resulting :class:`~repro.core.plancache.ChunkPlan`
        (``chunk_size`` + ``chunk_threshold``).  Probes compile into a
        throwaway scoped cache, so the serving cache's stats and programs
        are untouched.  Keyword args forward to
        :func:`repro.core.plancache.tune_chunking`."""
        from . import plancache

        plan = plancache.tune_chunking(self.backend, **kwargs)
        self.chunk_size = plan.chunk_size
        self.chunk_threshold = plan.chunk_threshold
        self.chunk_plan = plan
        return plan

    def _stage(self, name: str, sync: bool, fn, *args):
        """Run one stage under the span ``repro.rebuild.<name>``; barrier
        on its outputs only when ``sync``.

        Async mode leaves the outputs as in-flight device arrays — the next
        stage's dispatch overlaps their compute — so the returned wall (and
        the span) is dispatch time, not execution time."""
        with span("rebuild." + name):
            t0 = time.perf_counter()
            out = fn(*args)
            if sync:
                out = jax.tree_util.tree_map(
                    lambda x: x.block_until_ready()
                    if hasattr(x, "block_until_ready") else x,
                    out,
                )
            return out, time.perf_counter() - t0

    @staticmethod
    def _sync(*arrays) -> float:
        """Barrier on the run's result arrays; returns the blocked wall."""
        t0 = time.perf_counter()
        for a in arrays:
            if hasattr(a, "block_until_ready"):
                a.block_until_ready()
        return time.perf_counter() - t0

    def _sort_chunked(self, comp: jnp.ndarray, n: int, b: int,
                      donate_sorts: bool = False):
        """Large-N sort: bucket-aligned chunks + a binary-counter ladder of
        cached merges.

        Each chunk sorts with *local* rows (every chunk replays the same
        small-bucket cached program and satisfies the [0, m) row contract);
        the chunk offset is added afterwards, which preserves the sorted
        (key, row) order because the offset is monotone within the chunk.

        The fold is a binary counter, not a level-by-level pass: a run of
        2^k merged chunks merges with its equal-sized neighbor the moment
        that neighbor completes, so at most O(log n_chunks) runs are ever
        live at once (one per set bit of the chunks-so-far count) instead
        of one full level — the ``cascade_peak_live_runs`` stat records the
        observed peak, and popping merged runs off the stack drops their
        last references so the footprint tracks it.  With ``self.donate``
        the chunk sorts also run zero-copy in place (input and output
        buckets coincide).  Any association of cached ``merge_sorted``
        programs is
        byte-identical to one monolithic sort because a merge of sorted
        runs under the total (key, row) order has exactly one output.

        Runs stay bucket-padded end to end (``keep_padded`` + ``n_valid``
        chaining — no eager slice-and-re-pad between levels); one final
        ``pad_tail`` aligns the cascade total to the build bucket ``b``.
        Returns ``(b,)``-padded buffers.
        """
        from . import plancache

        c = self.chunk_size
        donate = self.donate
        # stack of live runs: (chunks_merged, n_valid, keys, rows); the
        # chunk counts are strictly decreasing, adjacent equals merge
        stack: list = []
        peak = 0
        merges = 0

        def _merge_top():
            nonlocal merges
            cb, nvb, kb, rb = stack.pop()
            ca, nva, ka, ra = stack.pop()
            mk, mr = self.backend.merge_sorted(
                ka, ra, kb, rb, n_valid_a=nva, n_valid_b=nvb,
                keep_padded=True, donate=donate,
            )
            stack.append((ca + cb, nva + nvb, mk, mr))
            merges += 1

        for s in range(0, n, c):
            m = min(c, n - s)
            chunk = comp[s : s + c]
            ck, cr = self.backend.sort(
                chunk, plancache.iota_u32(int(chunk.shape[0])),
                n_valid=m, keep_padded=True, donate=donate_sorts,
            )
            stack.append((1, m, ck, cr + jnp.uint32(s)))
            peak = max(peak, len(stack))
            while len(stack) >= 2 and stack[-1][0] == stack[-2][0]:
                _merge_top()
        while len(stack) > 1:  # fold the leftover ragged tail, smallest first
            _merge_top()
        _, nv, ks, rs = stack[0]
        self._last_cascade = {
            "cascade_peak_live_runs": peak,
            "cascade_merges": merges,
        }
        # align the cascade total (n_chunks * chunk bucket) to the build
        # bucket; identity when they already agree.  Pad *content* is
        # irrelevant — downstream programs renormalize from n_valid.
        if int(ks.shape[0]) != b:
            ks = plancache.pad_tail(ks, b, 0xFFFFFFFF)
            rs = plancache.pad_tail(rs, b, 0)
        return ks, rs

    # ---------------------------------------------------------------- run
    @spanned("rebuild")
    def run(
        self,
        keyset: KeySet,
        meta: DSMeta | None = None,
        full_keys: bool = False,
        watermark: int | None = None,
        publish_to=None,
        stage_timings: bool | None = None,
    ) -> ReconstructionResult:
        """Reconstruct one index.

        ``full_keys=True`` runs the uncompressed baseline (Figure 1 top
        flow): identity metadata, extraction skipped, the sort sees the full
        key width.  DS-metadata is then left as-is (the baseline has none to
        refresh).  ``watermark`` stamps the result with the LSN it is
        current through (replication consumers use it for lag accounting
        and to elide no-op rebuilds).  ``publish_to`` (a
        ``repro.core.snapshot.SnapshotCell``) atomically publishes the
        finished result as the cell's next snapshot epoch before returning.
        ``stage_timings`` overrides the pipeline's sync policy for this
        call: ``True`` restores the per-stage barriers (the Figure-9
        breakdown) even under ``async_dispatch``; ``False`` forces one
        end-of-run sync.  Either way the run returns fully materialized
        results and ``timings["sync"]`` reports the final barrier's wall.
        """
        from . import plancache

        t_run0 = time.perf_counter()
        sync = (stage_timings if stage_timings is not None
                else not self.async_dispatch)
        n = keyset.n
        b = plancache.bucket_for("sort", n)
        with span("rebuild.upload"):  # the host's part; adds no barrier
            rids = jnp.asarray(keyset.rids, jnp.uint32)
            lengths = jnp.asarray(keyset.lengths, jnp.int32)
            # enter the bucket world once: pad the full keys to the sort
            # bucket against cached constants (one dynamic_update_slice, no
            # per-call concatenate/fill) and take the cached iota as the row
            # ids.  Pad lane *content* is irrelevant from here on — every
            # cached program renormalizes its pads from the dynamic
            # valid-count operand.
            words_dev = plancache.pad_tail(
                jnp.asarray(keyset.words, jnp.uint32), b, 0xFFFFFFFF
            )
            rows_dev = plancache.iota_u32(b)

        t_meta = 0.0
        if full_keys:
            meta = identity_meta(keyset)
        elif meta is None:
            t0 = time.perf_counter()
            meta = meta_from_keys(keyset.words)
            t_meta = time.perf_counter() - t0
        plan = meta.plan()

        chunked = self.chunk_threshold is not None and n > self.chunk_threshold
        if chunked and self.auto_tune_chunks and self.chunk_plan is None:
            self.tune_chunking()
            chunked = n > self.chunk_threshold

        # Donation guards: ``words_dev`` is never donated (the build stage
        # reads it after the sort on the full-keys and fused paths, and the
        # caller's keyset aliases nothing else); when n == b the [:n]
        # result slices alias the padded buffers themselves (a full slice
        # is the identity), so build/refresh must not consume them either.
        donate = self.donate
        donate_results = donate and n < b

        # -- extract / sort (backend-dispatched, optionally fused) ---------
        fused_used = False
        chunks = 0
        if chunked:
            # the ladder: extraction stays one bucket-shaped program; the
            # sort splits into chunk-bucket programs + a merge ladder
            chunks = -(-n // self.chunk_size)
            if full_keys:
                comp, t_extract = words_dev, 0.0
            else:
                comp, t_extract = self._stage(
                    "extract", sync, self.extract, words_dev, plan
                )
            # chunk sorts consume their key slices — strict sub-slices are
            # fresh buffers even when comp is words_dev, but a single
            # clamped full slice *is* comp, so full_keys then opts out
            donate_sorts = donate and (not full_keys or chunks > 1)
            (comp_sorted_p, row_sorted_p), t_sort = self._stage(
                "sort", sync, lambda: self._sort_chunked(comp, n, b, donate_sorts)
            )
        elif full_keys:
            t_extract = 0.0
            (comp_sorted_p, row_sorted_p), t_sort = self._stage(
                "sort", sync,
                lambda: self.sort(words_dev, rows_dev, n_valid=n,
                                  keep_padded=True),
            )
        elif self.fused and self.backend.supports_fused:
            fused_used = True
            t_extract = 0.0
            (comp_sorted_p, row_sorted_p), t_sort = self._stage(
                "sort", sync,
                lambda: self.backend.fused_extract_sort(
                    words_dev, plan, rows_dev, n_valid=n, keep_padded=True
                ),
            )
        else:
            comp, t_extract = self._stage(
                "extract", sync, self.extract, words_dev, plan
            )
            # comp is the extract output and dies with the sort
            (comp_sorted_p, row_sorted_p), t_sort = self._stage(
                "sort", sync,
                lambda: self.sort(comp, rows_dev, n_valid=n, keep_padded=True,
                                  donate=donate),
            )
        row_sorted_p = jnp.asarray(row_sorted_p, jnp.uint32)
        comp_sorted = comp_sorted_p[:n]
        row_sorted = row_sorted_p[:n]
        rid_sorted = rids[row_sorted]

        # -- build (padded buffers chain straight in; n_valid carries the
        # -- real count, so no slice-and-re-pad between the stages).  The
        # -- build may consume row_sorted_p (its scratch) once the result
        # -- slices above are dispatched ------------------------------------
        tree, t_build = self._stage(
            "build", sync,
            lambda: self.build(
                comp_sorted_p, row_sorted_p, meta, words_dev, lengths, rids,
                n_valid=n, donate=donate_results,
            ),
        )

        # -- refresh DS-metadata (opportune time, §4.3); last consumer of
        # -- comp_sorted_p, so it may take the buffer --------------------------
        t_refresh = 0.0
        new_meta = meta
        if not full_keys:
            with span("rebuild.refresh"):
                t0 = time.perf_counter()
                new_meta = self.refresh_meta(
                    comp_sorted_p, meta, keyset.words[0], n_valid=n,
                    donate=donate_results,
                )
                t_refresh = time.perf_counter() - t0

        t_sync = 0.0 if sync else self._sync(comp_sorted, row_sorted, rid_sorted)
        timings = {
            "meta": t_meta,
            "extract": t_extract,
            "sort": t_sort,
            "build": t_build,
            "refresh_meta": t_refresh,
            "sync": t_sync,
            "total": (t_extract + t_sort + t_build) if sync
            else time.perf_counter() - t_run0,
        }
        stats = self._stats(keyset, meta, comp_sorted, row_sorted, tree, fused_used)
        stats["chunked"] = chunks
        stats["async_dispatch"] = not sync
        stats["donate"] = donate
        stats["chunk_size"] = self.chunk_size
        stats["chunk_threshold"] = self.chunk_threshold
        stats["chunk_tuned"] = self.chunk_plan is not None
        if chunks:
            stats.update(self._last_cascade)
        res = ReconstructionResult(
            tree=tree,
            meta=new_meta,
            comp_sorted=comp_sorted,
            rid_sorted=rid_sorted,
            timings=timings,
            stats=stats,
            row_sorted=row_sorted,
            extract_bitmap=np.array(meta.dbitmap, np.uint32, copy=True),
            watermark=watermark,
        )
        if publish_to is not None:
            publish_to.publish(res)
        return res

    # -------------------------------------------------- incremental (delta)
    @spanned("rebuild")
    def run_incremental(
        self,
        prev: ReconstructionResult,
        base_keyset: KeySet,
        delta_keyset: KeySet | None = None,
        *,
        keep_rows: np.ndarray | None = None,
        meta: DSMeta | None = None,
        watermark: int | None = None,
        publish_to=None,
        stage_timings: bool | None = None,
    ) -> tuple[ReconstructionResult, KeySet]:
        """Fold a change set into ``prev`` without re-sorting the base.

        ``base_keyset`` must be the keyset ``prev`` was reconstructed from;
        ``keep_rows`` masks deleted base row positions; ``delta_keyset``
        holds inserted rows (appended after the surviving base rows, which
        is exactly the row numbering a full ``run`` over the folded keyset
        sees).  ``meta`` is the *current* DS-metadata — the caller maintains
        it across mutations via the §4.3 insert rule (defaults to
        ``prev.meta``).

        Returns ``(result, folded_keyset)``.  The result is byte-identical —
        sorted compressed keys, rid permutation, tree levels — to
        ``self.run(folded_keyset, meta=meta)``:

        * surviving base rows keep their relative (key, row) order because
          deletion renumbers rows monotonically;
        * the delta is extracted and sorted through the normal backend
          stages, with row ids offset past the surviving base rows;
        * ``backend.merge_sorted`` interleaves the two runs under the same
          (key, row) contract the sort stage obeys.

        Falls back to the full path (with ``stats["incremental"] = False``
        and the reason in ``stats["incremental_fallback"]``) when the
        D-bitmap changed since ``prev``'s extraction — the compressed
        projection moved, so ``prev.comp_sorted`` can no longer be merged
        against (e.g. an online insert set a new distinction bit and the
        compressed width or bit set grew).

        ``watermark`` stamps the result with the LSN it is current through.
        A change set that is *empty* (no deletes, no delta) under unchanged
        metadata short-circuits entirely: the previous result is returned
        re-stamped at the new watermark (``stats["noop"] = True``) without
        touching the device — the heartbeat-batch fast path of the stream
        layer.  The short-circuit preserves byte-identity because ``prev``
        already equals a full ``run`` over the (unchanged) folded keyset.

        ``publish_to`` publishes the result — whichever path produced it,
        the no-op re-stamp included — as the cell's next snapshot epoch,
        so a reader pinned on the pre-rebuild epoch keeps serving it while
        this method runs and epochs stay aligned with watermarks.
        """
        if meta is None:
            meta = prev.meta
        folded = fold_keyset(base_keyset, keep_rows, delta_keyset)
        n_delta = 0 if delta_keyset is None else delta_keyset.n

        fallback = None
        if prev.extract_bitmap is None:
            fallback = "no_extract_bitmap"
        elif not np.array_equal(
            np.asarray(meta.dbitmap, np.uint32), prev.extract_bitmap
        ):
            fallback = "dbitmap_changed"
        t_run0 = time.perf_counter()
        sync = (stage_timings if stage_timings is not None
                else not self.async_dispatch)
        if fallback is not None:
            res = self.run(folded, meta=meta, watermark=watermark,
                           stage_timings=stage_timings)
            res.stats["incremental"] = False
            res.stats["incremental_fallback"] = fallback
            if publish_to is not None:
                publish_to.publish(res)
            return res, folded

        # -- empty change set: advance the watermark, skip the rebuild -----
        if (
            n_delta == 0
            and (keep_rows is None or bool(np.asarray(keep_rows, bool).all()))
            and (
                meta is prev.meta
                or np.array_equal(meta.varbitmap, prev.meta.varbitmap)
            )
        ):
            stats = dict(prev.stats)
            stats.update(incremental=True, noop=True, n_delta=0, n_deleted=0)
            stats.pop("incremental_fallback", None)
            timings = {
                k: 0.0
                for k in ("meta", "filter", "extract", "sort", "merge",
                          "build", "refresh_meta", "sync", "total")
            }
            res = _dc_replace(
                prev, timings=timings, stats=stats, watermark=watermark
            )
            if publish_to is not None:
                publish_to.publish(res)
            return res, folded

        plan = meta.plan()

        # -- filter the surviving base run (device-side mask, no re-sort) --
        def _filter():
            if keep_rows is None:
                return prev.comp_sorted, jnp.asarray(prev.row_sorted, jnp.uint32)
            keep = jnp.asarray(np.asarray(keep_rows, bool))
            keep_sorted = keep[prev.row_sorted]
            # deletion renumbers surviving rows monotonically, so the kept
            # run stays ascending in (key, new row)
            new_row = jnp.cumsum(keep.astype(jnp.int32)) - 1
            base_comp = prev.comp_sorted[keep_sorted]
            base_rows = new_row[prev.row_sorted][keep_sorted].astype(jnp.uint32)
            return base_comp, base_rows

        (base_comp, base_rows), t_filter = self._stage("filter", sync, _filter)
        n_kept = int(base_comp.shape[0])

        # -- extract + sort only the delta.  The delta's compressed keys
        # -- die with the sort, so they may be donated; the *base* run is
        # -- prev.comp_sorted (or a view of it) and is never donated — the
        # -- caller's previous result must survive this call ---------------
        t_extract = t_sort = 0.0
        if n_delta:
            delta_words = jnp.asarray(delta_keyset.words, jnp.uint32)
            comp_delta, t_extract = self._stage(
                "extract", sync, self.extract, delta_words, plan
            )
            (comp_delta_sorted, rows_delta), t_sort = self._stage(
                "sort", sync,
                lambda: self.sort(
                    comp_delta, jnp.arange(n_delta, dtype=jnp.uint32),
                    donate=self.donate,
                ),
            )
            # delta rows live after every surviving base row in the folded
            # numbering; the offset preserves the sorted (key, row) order
            rows_delta = jnp.asarray(rows_delta, jnp.uint32) + jnp.uint32(n_kept)
        else:
            comp_delta_sorted = jnp.zeros((0, base_comp.shape[1]), jnp.uint32)
            rows_delta = jnp.zeros((0,), jnp.uint32)

        # -- merge the runs (the backend op) -------------------------------
        (comp_sorted, row_sorted), t_merge = self._stage(
            "merge", sync, self.backend.merge_sorted,
            base_comp, base_rows, comp_delta_sorted, rows_delta,
        )
        row_sorted = jnp.asarray(row_sorted, jnp.uint32)
        rid_sorted = jnp.asarray(folded.rids, jnp.uint32)[row_sorted]

        # -- build + refresh (identical to the full path; no donation —
        # -- comp_sorted/row_sorted ARE the result arrays here) ------------
        words = jnp.asarray(folded.words, jnp.uint32)
        lengths = jnp.asarray(folded.lengths, jnp.int32)
        rids = jnp.asarray(folded.rids, jnp.uint32)
        tree, t_build = self._stage(
            "build", sync, self.build, comp_sorted, row_sorted, meta, words,
            lengths, rids,
        )
        with span("rebuild.refresh"):
            t0 = time.perf_counter()
            new_meta = self.refresh_meta(comp_sorted, meta, folded.words[0])
            t_refresh = time.perf_counter() - t0

        t_sync = 0.0 if sync else self._sync(comp_sorted, row_sorted, rid_sorted)
        timings = {
            "meta": 0.0,
            "filter": t_filter,
            "extract": t_extract,
            "sort": t_sort,
            "merge": t_merge,
            "build": t_build,
            "refresh_meta": t_refresh,
            "sync": t_sync,
            "total": (t_filter + t_extract + t_sort + t_merge + t_build)
            if sync else time.perf_counter() - t_run0,
        }
        stats = self._stats(folded, meta, comp_sorted, row_sorted, tree, False)
        stats["incremental"] = True
        stats["n_delta"] = n_delta
        stats["n_deleted"] = base_keyset.n - n_kept
        stats["async_dispatch"] = not sync
        stats["donate"] = self.donate
        res = ReconstructionResult(
            tree=tree,
            meta=new_meta,
            comp_sorted=comp_sorted,
            rid_sorted=rid_sorted,
            timings=timings,
            stats=stats,
            row_sorted=row_sorted,
            extract_bitmap=np.array(meta.dbitmap, np.uint32, copy=True),
            watermark=watermark,
        )
        if publish_to is not None:
            publish_to.publish(res)
        return res, folded

    @spanned("rebuild.stats")  # its float(...) calls wait for the device
    def _stats(self, keyset, meta, comp_sorted, row_sorted, tree, fused_used):
        full_bits = keyset.n_bits
        # wcc over the *row*-permuted full keys: row_sorted indexes rows of
        # the table; rids are labels, not positions.
        full_sorted = jnp.asarray(keyset.words, jnp.uint32)[row_sorted]
        stats = {
            "backend": self.backend.name,
            "fused": fused_used,
            "n_keys": keyset.n,
            "full_key_bits": full_bits,
            "distinction_bits": meta.n_dbits,
            "compression_ratio": full_bits / max(meta.n_dbits, 1),
            "full_sort_key_words": keyset.n_words + 1,  # + rid word
            "comp_sort_key_words": int(comp_sorted.shape[1]) + 1,
            "sort_key_ratio": (keyset.n_words + 1) / (int(comp_sorted.shape[1]) + 1),
            "wcc_full": float(word_comparison_counts(full_sorted)),
            "wcc_comp": float(word_comparison_counts(comp_sorted)),
            "tree_height": tree.height,
            "tree_bytes": tree.memory_bytes(),
        }
        stats["word_comparison_ratio"] = stats["wcc_full"] / max(stats["wcc_comp"], 1e-9)
        stats.update(self.backend.last_info)
        return stats

    # ----------------------------------------------------- batched (many)
    def run_many(
        self,
        keysets: list[KeySet],
        metas: list[DSMeta | None] | None = None,
    ) -> list[ReconstructionResult]:
        """Reconstruct many independent indexes (the replication scenario).

        Same-shape key sets on a backend with ``supports_batched`` are
        batched: the stacked extract+sort dispatches to the backend's
        ``batched_extract_sort`` (one vmapped dynamic-bitmap program on jnp;
        per-plan pext kernels + one vmapped bitonic sort program on pallas),
        then a per-index build loop.  Heterogeneous shapes — and backends
        without the capability, e.g. distributed, whose exchange owns the
        whole mesh — fall back to sequential ``run``.
        """
        if metas is None:
            metas = [None] * len(keysets)
        if len(metas) != len(keysets):
            raise ValueError("metas must align with keysets")

        results: list[ReconstructionResult | None] = [None] * len(keysets)

        if not self.backend.supports_batched:
            return [self.run(ks, meta=m) for ks, m in zip(keysets, metas)]

        # metadata first (it determines the compressed width), then group by
        # (shape bucket, n_words, compressed width): members of a bucket pad
        # to the bucket boundary with sentinel rows, so the stacked program
        # is shared across drifting sizes AND every member still gets
        # exactly the comp_sorted its own single run would produce
        t0 = time.perf_counter()
        metas = [
            m if m is not None else meta_from_keys(ks.words)
            for ks, m in zip(keysets, metas)
        ]
        t_meta_total = time.perf_counter() - t0

        from . import plancache

        groups: dict[tuple[int, int, int], list[int]] = {}
        for i, (ks, m) in enumerate(zip(keysets, metas)):
            groups.setdefault(
                (
                    plancache.bucket_for("run_many", ks.n),
                    ks.n_words,
                    m.plan().n_words_out,
                ),
                [],
            ).append(i)

        t_meta = t_meta_total / max(len(keysets), 1)
        for _, idxs in groups.items():
            if len(idxs) < 2:
                for i in idxs:
                    results[i] = self.run(keysets[i], meta=metas[i])
                continue
            for i, res in zip(idxs, self._run_batched(
                [keysets[i] for i in idxs], [metas[i] for i in idxs], t_meta
            )):
                results[i] = res
        return results  # type: ignore[return-value]

    def _run_batched(self, keysets, metas, t_meta) -> list[ReconstructionResult]:
        from . import plancache

        k = len(keysets)
        plans = [m.plan() for m in metas]
        b = plancache.bucket_for("run_many", max(ks.n for ks in keysets))
        # members pad to the shared bucket boundary: all-ones sentinel keys
        # extract to the maximal compressed pattern and the reserved row-id
        # range breaks ties, so each member's pads sort strictly last and
        # slicing [:n] recovers its exact single-run output
        words = jnp.asarray(
            np.stack([
                np.concatenate([
                    np.asarray(ks.words, np.uint32),
                    np.full((b - ks.n, ks.n_words), 0xFFFFFFFF, np.uint32),
                ])
                for ks in keysets
            ]),
            jnp.uint32,
        )
        bitmaps = jnp.asarray(np.stack([m.dbitmap for m in metas]), jnp.uint32)
        rows = jnp.asarray(
            np.stack([
                np.concatenate([
                    np.arange(ks.n, dtype=np.uint32),
                    np.uint32(plancache.ROW_PAD_A)
                    + np.arange(b - ks.n, dtype=np.uint32),
                ])
                for ks in keysets
            ]),
            jnp.uint32,
        )

        # the stacked extract+sort is the backend's batched program (keyed
        # sort — the determinism contract — on whatever substrate it runs)
        (comp_sorted, row_sorted), t_xs = _timed(
            self.backend.batched_extract_sort, words, bitmaps, rows, plans
        )

        out = []
        for i, (ks, meta) in enumerate(zip(keysets, metas)):
            cs, rs = comp_sorted[i, : ks.n], row_sorted[i, : ks.n]
            rids = jnp.asarray(ks.rids, jnp.uint32)
            lengths = jnp.asarray(ks.lengths, jnp.int32)
            tree, t_build = _timed(
                self.build, cs, rs, meta, jnp.asarray(ks.words, jnp.uint32),
                lengths, rids,
            )
            t0 = time.perf_counter()
            new_meta = self.refresh_meta(cs, meta, ks.words[0])
            t_refresh = time.perf_counter() - t0
            timings = {
                "meta": t_meta,
                "extract": 0.0,
                "sort": t_xs / k,
                "build": t_build,
                "refresh_meta": t_refresh,
                "total": t_xs / k + t_build,
            }
            # "batched" carries the batching fact; "fused" stays reserved
            # for the backend's fused_extract_sort path
            stats = self._stats(ks, meta, cs, rs, tree, fused_used=False)
            stats["batched"] = k
            out.append(
                ReconstructionResult(
                    tree=tree,
                    meta=new_meta,
                    comp_sorted=cs,
                    rid_sorted=rids[rs],
                    timings=timings,
                    stats=stats,
                    row_sorted=rs,
                    extract_bitmap=np.array(meta.dbitmap, np.uint32, copy=True),
                )
            )
        return out
