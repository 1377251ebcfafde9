"""A replica that consumes change-log batches and rebuilds incrementally.

The paper's replication premise: the wire carries the table (here: the base
keyset once, then ``ChangeLog`` batches) and the DS-metadata — never an
index image.  ``Replica`` keeps the reconstructed index current by folding
each log batch through ``ReconstructionPipeline.run_incremental``: delete
entries become a keep-mask over the base rows, surviving inserts become the
delta keyset, and only the delta is extracted and sorted before the backend
``merge_sorted`` splices it into the standing run.  When a batch's keys add
new distinction bits the pipeline transparently falls back to the full
rebuild (same result, full cost) — the replica's answer is byte-identical
either way.

DS-metadata upkeep is the §4.3 insert rule, vectorized: every inserted key
finds its neighbors (A, B) in the standing sorted order with one batched
rank search, and D(A,K) / D(K,B) are OR-scattered into the D-bitmap in one
shot.  Setting both is exactly the paper's "set max(D(A,K), D(K,B))"
because the min equals D(A,B), which Lemma 1 guarantees is already set.
Delta-internal adjacency is covered by the delta's own D-bitmap.
"""

from __future__ import annotations

import time
from dataclasses import replace

import jax.numpy as jnp
import numpy as np

from repro.core.btree import BTreeConfig
from repro.core.dbits import (
    NO_DBIT,
    compute_dbitmap,
    dbit_position_pairwise,
    positions_to_bitmap,
    rank_in_sorted_keyed,
)
from repro.core.keyformat import KeySet  # noqa: F401  (public API type)
from repro.core.metadata import DSMeta, shed_or_pin
from repro.core.pipeline import ReconstructionPipeline, ReconstructionResult
from repro.core.snapshot import SnapshotCell
from repro.core.spans import span, spanned

from .log import ChangeLog

__all__ = ["Replica"]


class Replica:
    """One replicated index: base bring-up + incremental log consumption.

    Parameters
    ----------
    keyset:             the base table rows (bring-up reconstructs from it).
    meta:               DS-metadata to extract under; ``None`` derives it
                        from the keys.  A catch-up bootstrap passes the
                        checkpointed *working* metadata here, which is what
                        makes the bootstrapped state byte-identical to a
                        never-lagged replica's (see ``stream.StreamReplica``).
    backend:            execution backend name for all rebuilds.
    config:             B-tree geometry.
    backend_opts:       forwarded to the backend constructor.
    shed_delete_frac:   bitmap shed threshold (``None`` = always pin).
    applied_lsn:        LSN watermark this base state is current through
                        (``-1`` = nothing applied; a bootstrap resumes at
                        the checkpoint's watermark).
    deletes_since_shed: resume value for the shed-policy volume counter.
    snapshot_epoch:     epoch the bring-up snapshot is published at (a
                        checkpoint bootstrap resumes the primary's
                        numbering; the default starts at 0).
    """

    def __init__(
        self,
        keyset: KeySet,
        meta: DSMeta | None = None,
        backend: str = "jnp",
        config: BTreeConfig = BTreeConfig(),
        backend_opts: dict | None = None,
        shed_delete_frac: float | None = None,
        applied_lsn: int = -1,
        deletes_since_shed: int = 0,
        snapshot_epoch: int = 0,
    ) -> None:
        self.pipeline = ReconstructionPipeline(
            backend=backend, config=config, backend_opts=backend_opts
        )
        self.keyset = keyset
        # the versioned read path: every rebuild publishes the next epoch
        # here and every search pins the current one (double buffering)
        self.snapshots = SnapshotCell(start_epoch=int(snapshot_epoch) - 1)
        self.result: ReconstructionResult = self.pipeline.run(
            keyset, meta=meta, watermark=applied_lsn if applied_lsn >= 0 else None,
            publish_to=self.snapshots,
        )
        # the working metadata mirrors the *extraction* bitmap (plus insert
        # bits as batches arrive): keeping it pinned to what comp_sorted was
        # extracted under is what lets consecutive batches stay incremental
        self._meta = replace(
            self.result.meta,
            dbitmap=np.array(self.result.extract_bitmap, np.uint32, copy=True),
        )
        # bitmap shed policy: pinning keeps rebuilds incremental but lets
        # delete-stale distinction bits accumulate (wider compressed keys).
        # When the delete volume since the bits were last re-derived crosses
        # ``shed_delete_frac`` of the index size, adopt the refreshed
        # (shed) bitmap instead — the next batch pays one full resort under
        # the narrower projection, then pinning resumes.  ``None`` never
        # sheds (the PR-2 behavior).
        self.shed_delete_frac = shed_delete_frac
        self._deletes_since_shed = int(deletes_since_shed)
        self.applied_lsn = int(applied_lsn)
        self.n_applied_batches = 0

    @property
    def tree(self):
        """The standing partial-key B+tree (current reconstruction)."""
        return self.result.tree

    @property
    def meta(self) -> DSMeta:
        """The working DS-metadata (pinned/shed per the bitmap policy)."""
        return self._meta

    @property
    def deletes_since_shed(self) -> int:
        """Delete volume since the D-bitmap was last re-derived (shed
        policy bookkeeping; snapshotted into checkpoint frames)."""
        return self._deletes_since_shed

    @property
    def stats(self) -> dict:
        """Health snapshot of the standing index: watermark, size, shed
        bookkeeping, snapshot epoch — the inner-replica half of the
        counters a stream consumer (or its supervisor) surfaces."""
        return {
            "applied_lsn": self.applied_lsn,
            "n_applied_batches": self.n_applied_batches,
            "n_keys": self.keyset.n,
            "watermark": self.result.watermark,
            "deletes_since_shed": self._deletes_since_shed,
            "shed_delete_frac": self.shed_delete_frac,
            "snapshot_epoch": self.snapshots.epoch,
        }

    # ------------------------------------------------------------- lookup
    def search_batch(
        self, query_words: np.ndarray
    ) -> tuple[np.ndarray, np.ndarray]:
        """Batched point lookup: (q, W) keys -> ((q,) found, (q,) rid).

        Pins the current snapshot epoch and probes it with the backend's
        plan-cached ``lookup`` op — a query stream interleaved with
        ``apply`` keeps answering from the pre-rebuild epoch until the new
        one is published, never a torn mixture.  Miss lanes carry
        ``repro.core.btree.NOT_FOUND_RID``.
        """
        q = jnp.asarray(
            np.asarray(query_words, np.uint32).reshape(-1, self.keyset.n_words)
        )
        with self.snapshots.pin() as snap:
            found, rid = self.pipeline.backend.lookup(snap.tree, q)
        return np.asarray(found, bool), np.asarray(rid, np.uint32)

    def search(self, query_words: np.ndarray) -> tuple[bool, int]:
        """Point lookup through the pinned snapshot: ``(found, rid)``.

        A thin wrapper over :meth:`search_batch` (one implementation for
        scalar and batched lookups).
        """
        found, rid = self.search_batch(
            np.asarray(query_words, np.uint32)[None, :]
        )
        return bool(found[0]), int(rid[0])

    # -------------------------------------------------------------- apply
    def apply_many(self, logs: "list[ChangeLog]") -> dict:
        """Fold several LSN-contiguous batches through ONE rebuild.

        The watermark-triggered form of ``apply``: a consumer that drained
        multiple pending stream batches stitches them (``ChangeLog.concat``
        checks contiguity) and pays one fold + one incremental
        reconstruction for the whole span, instead of one rebuild per
        batch.  Returns the same stats dict as ``apply``.
        """
        return self.apply(ChangeLog.concat(logs))

    @spanned("replica.apply")
    def apply(self, log: ChangeLog) -> dict:
        """Fold one log batch into the standing index.

        Deletes become a keep-mask over the base rows, surviving inserts
        the delta keyset; DS-metadata advances by the vectorized §4.3
        insert rule *before* the rebuild so the extraction plan covers the
        batch.  The rebuild runs ``ReconstructionPipeline.run_incremental``
        — byte-identical to a full ``run`` over the folded keyset (empty
        batches short-circuit through the pipeline's no-op fast path and
        only advance the watermark).  Returns apply stats: which path ran
        (``incremental`` / ``fallback`` / ``noop``), churn counts, shed
        policy state, the new ``applied_lsn``, and per-stage timings: the
        pipeline's, plus ``fold`` (the log replayed against the base rows
        on the host) and ``insert_rule`` (the §4.3 metadata upkeep, which
        waits for the device), in seconds.
        """
        if log.n_words != self.keyset.n_words:
            raise ValueError(
                f"log key width {log.n_words} != index width {self.keyset.n_words}"
            )
        with span("replica.fold"):
            t0 = time.perf_counter()
            keep_rows, delta = log.fold_keyset(self.keyset)
            t_fold = time.perf_counter() - t0
        n_delta = 0 if delta is None else delta.n
        n_deleted = 0 if keep_rows is None else int(self.keyset.n - keep_rows.sum())
        meta, t_rule = self._meta, 0.0
        if n_delta:
            with span("replica.insert_rule"):
                t0 = time.perf_counter()
                meta = self._insert_rule(delta.words)
                t_rule = time.perf_counter() - t0

        res, folded = self.pipeline.run_incremental(
            self.result, self.keyset, delta, keep_rows=keep_rows, meta=meta,
            watermark=log.next_lsn - 1, publish_to=self.snapshots,
        )
        self.keyset, self.result = folded, res
        self._meta, shed, self._deletes_since_shed = shed_or_pin(
            res.meta, res.extract_bitmap,
            self._deletes_since_shed + n_deleted,
            self.shed_delete_frac, folded.n,
        )
        self.applied_lsn = log.next_lsn - 1
        self.n_applied_batches += 1
        return {
            "incremental": bool(res.stats.get("incremental")),
            "fallback": res.stats.get("incremental_fallback"),
            "noop": bool(res.stats.get("noop", False)),
            "n_delta": n_delta,
            "n_deleted": n_deleted,
            "n_keys": folded.n,
            "shed_bits": shed,
            "deletes_since_shed": self._deletes_since_shed,
            "applied_lsn": self.applied_lsn,
            "timings": {**res.timings, "fold": t_fold, "insert_rule": t_rule},
        }

    # ------------------------------------------------------- shed adoption
    def adopt_shed(self) -> bool:
        """Adopt the refreshed (shed) D-bitmap of the last rebuild *now*.

        The stream-driven form of the shed policy: instead of evaluating
        ``shed_delete_frac`` locally (whose per-rebuild cadence diverges
        between replicas that poll at different rates), a consumer adopts
        sheds exactly where the primary logged them — the shed control
        frame in the stream names the watermark, and this call flips the
        working metadata from the pinned extraction bitmap to the
        refreshed one, so the next rebuild pays the one full resort under
        the narrower projection just as the primary's did.  Returns
        whether the bitmap actually changed (idempotent on a replica that
        already shed locally).
        """
        refreshed = self.result.meta
        changed = not np.array_equal(
            np.asarray(self._meta.dbitmap, np.uint32),
            np.asarray(refreshed.dbitmap, np.uint32),
        )
        self._meta = refreshed
        self._deletes_since_shed = 0
        return changed

    # ---------------------------------------------------- metadata upkeep
    def _insert_rule(self, ins_words: np.ndarray) -> DSMeta:
        """§4.3 insert rule for a whole batch, no host loop."""
        meta = self._meta
        sf = self.result.tree.sorted_full  # standing sorted full keys
        n = int(sf.shape[0])
        k = jnp.asarray(ins_words, jnp.uint32)
        m = int(k.shape[0])
        zeros_s = jnp.zeros((n,), jnp.uint32)
        zeros_q = jnp.zeros((m,), jnp.uint32)
        # strict-key rank: row tie-break never fires with equal row ids
        rank = rank_in_sorted_keyed(sf, zeros_s, k, zeros_q)
        has_a = rank > 0
        has_b = rank < n
        a = sf[jnp.clip(rank - 1, 0, n - 1)]
        b = sf[jnp.clip(rank, 0, n - 1)]
        d_ak = jnp.where(has_a, dbit_position_pairwise(a, k), NO_DBIT)
        d_kb = jnp.where(has_b, dbit_position_pairwise(k, b), NO_DBIT)
        nw = meta.n_words
        bm = positions_to_bitmap(jnp.concatenate([d_ak, d_kb]), nw)
        # delta-internal adjacency (keys that end up next to each other)
        bm = bm | compute_dbitmap(k)
        dbitmap = np.asarray(bm, np.uint32) | meta.dbitmap
        var = meta.varbitmap | np.bitwise_or.reduce(
            np.asarray(ins_words, np.uint32) ^ meta.refkey[None, :], axis=0
        )
        return replace(meta, dbitmap=dbitmap, varbitmap=var)
