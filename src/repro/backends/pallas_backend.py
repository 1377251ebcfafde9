"""Pallas-kernel backend: PEXT plane extraction + bitonic VMEM block sort.

Extraction runs through ``repro.kernels.pext`` (the static shift/mask
schedule over word planes); the sort runs the paper's row-column structure
on one device: ``repro.kernels.bitonic`` sorts VMEM-sized blocks, then one
``lax.sort`` merges the block runs (Appendix A step 3.2's multiway merge).
``merge_sorted`` has no kernel: the backend keeps the base class's XLA
merge-path merge (``last_info["merge"] == "xla"``).  A lane gather has no
Mosaic lowering, and the merge ladder's runs of millions of keys do not
fit one VMEM block.

``interpret`` is auto-selected from the platform: on TPU the kernels are
compiled by Mosaic; elsewhere the kernel *bodies* execute under the Pallas
interpreter so the same code path is validated on CPU CI.

The merge carries the row id as an extra least-significant key word: the
bitonic network is not stable, so ties must be re-broken on the row id to
meet the backend determinism contract (byte-identical output vs the jnp
oracle).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.compress import ExtractionPlan
from repro.core.dbits import sort_words_keyed
from repro.core.plancache import get_cache, sort_padded
from repro.kernels.bitonic import ops as bitonic_ops
from repro.kernels.bitonic.kernel import DEFAULT_BLOCK
from repro.kernels.build import ops as build_ops
from repro.kernels.build.kernel import DEFAULT_TILE as BUILD_TILE
from repro.kernels.lookup import ops as lookup_ops
from repro.kernels.lookup.kernel import DEFAULT_TILE as LOOKUP_TILE
from repro.kernels.pext import ops as pext_ops
from repro.kernels.pext.kernel import DEFAULT_TILE

from .base import ExecutionBackend, register_backend

__all__ = ["PallasBackend"]


@register_backend("pallas")
class PallasBackend(ExecutionBackend):
    """kernels/pext extraction + kernels/bitonic block sort; ``merge_sorted``
    is the inherited XLA merge, with no kernel (see the module docstring)."""

    supports_batched = True

    def __init__(
        self,
        interpret: bool | None = None,
        tile: int = DEFAULT_TILE,
        block: int = DEFAULT_BLOCK,
        build_tile: int = BUILD_TILE,
        lookup_tile: int = LOOKUP_TILE,
    ) -> None:
        super().__init__()
        if interpret is None:
            interpret = jax.default_backend() != "tpu"
        self.interpret = bool(interpret)
        self.tile = int(tile)
        self.block = int(block)
        self.build_tile = int(build_tile)
        self.lookup_tile = int(lookup_tile)
        self.last_info = {"interpret": self.interpret, "merge": "xla"}

    def extract(self, words: jnp.ndarray, plan: ExtractionPlan) -> jnp.ndarray:
        return pext_ops.pext(
            jnp.asarray(words, jnp.uint32),
            plan,
            tile=self.tile,
            interpret=self.interpret,
        )

    def sort(self, keys, rows, *, n_valid=None, keep_padded=False, donate=False):
        block, interpret = self.block, self.interpret

        def impl(kp, rp):
            bk, brow = bitonic_ops.block_sort(kp, rp, block=block, interpret=interpret)
            # merge of block-sorted runs; the keyed sort restores the
            # (key, row) order the unstable bitonic network does not
            # guarantee
            return sort_words_keyed(bk, brow)

        return sort_padded(
            jnp.asarray(keys, jnp.uint32), jnp.asarray(rows, jnp.uint32),
            backend=self.name, impl=impl, extra_key=(block, interpret),
            n_valid=n_valid, keep_padded=keep_padded, donate=donate,
        )

    def build(self, comp_sorted, row_sorted, meta, words, lengths, config,
              rids=None, n_valid=None, donate=False):
        """Cached build programs with the kernels/build tiled pk-window
        gather substituted for the jnp ``_slice_bits`` (bit-identical)."""
        from repro.core.btree import build_btree

        return build_btree(
            comp_sorted, row_sorted, meta, words, lengths, config, rids=rids,
            backend_name=self.name,
            slice_fn=build_ops.slice_fn(interpret=self.interpret, tile=self.build_tile),
            program_key_extra=(self.build_tile, self.interpret),
            n_valid=n_valid, donate=donate,
        )

    def lookup(self, tree, queries):
        """Plan-cached descent with the kernels/lookup partial-key probe
        at the leaf: candidates are screened by the tiled window kernel
        and confirmed with the full-key compare — byte-identical to the
        jnp oracle's unscreened compare by construction."""
        from repro.core.btree import lookup_batch_planned

        return lookup_batch_planned(
            tree,
            jnp.asarray(queries, jnp.uint32),
            backend_name=self.name,
            leaf_match_fn=lookup_ops.leaf_match_fn(
                tile=self.lookup_tile, interpret=self.interpret
            ),
            program_key_extra=(self.lookup_tile, self.interpret),
        )

    def lookup_many(self, stacked, queries, n_valid=None):
        """Fused multi-tenant lookup with the tenant-major probe kernel:
        the vmapped descent routes every tenant's queries, then ONE
        ``pallas_call`` over a (T, pairs/tile) grid screens all (tenant,
        query, entry) pairs before the full-key confirm — byte-identical
        per tenant to the single-snapshot pallas :meth:`lookup`."""
        from repro.core.btree import lookup_many_planned

        return lookup_many_planned(
            stacked,
            jnp.asarray(queries, jnp.uint32),
            n_valid,
            backend_name=self.name,
            leaf_match_many_fn=lookup_ops.leaf_match_many_fn(
                tile=self.lookup_tile, interpret=self.interpret
            ),
            program_key_extra=(self.lookup_tile, self.interpret),
        )

    def batched_extract_sort(self, words, bitmaps, rows, plans):
        """Batched fast path: per-index pext extraction (each plan is a
        static kernel schedule), then ONE vmapped program over the stacked
        batch for the sort — the bitonic block-sort kernel vmaps by growing
        its grid, and the run merge rides along inside the same trace.  The
        vmapped sort program is memoized in the plan cache per stacked
        shape, so repeated replication batches replay it."""
        del bitmaps  # pext wants the static plans, not runtime bitmaps
        comp = jnp.stack(
            [
                pext_ops.pext(words[i], p, tile=self.tile, interpret=self.interpret)
                for i, p in enumerate(plans)
            ]
        )
        cache = get_cache()
        k, n, wc = (int(s) for s in comp.shape)
        block, interpret = self.block, self.interpret

        def builder():
            def one(c, r):
                bk, brow = bitonic_ops.block_sort(c, r, block=block, interpret=interpret)
                return sort_words_keyed(bk, brow)

            return cache.jit(jax.vmap(one))

        prog = cache.program(
            ("run_many", self.name, k, n, wc, block, interpret), builder
        )
        return prog(comp, jnp.asarray(rows, jnp.uint32))
