"""Bottom-up bulk build of the partial-key B+tree (paper §4.2, §5.3).

TPU adaptation (DESIGN.md §2): pointer-chasing nodes become
structure-of-arrays *levels* — each level is a dict of `(n_nodes, fanout)`
arrays — so bulk build is reshapes + gathers and batched search is a
vectorized descent.  Entry layout is the paper's: every entry carries a
``pk``-bit partial key, the distinction bit position against the previous
entry's (highest) key, the key length, and a record id (leaf) or child
pointer + highest-key pointer (non-leaf).

Node geometry follows §5.3 exactly: 256-byte nodes, 24-byte header (+8-byte
next pointer in leaves), 16-byte leaf entries and 24-byte non-leaf entries
=> max fanout 14 (leaf) / 9 (non-leaf), filled to ``max_fanout * fill``
(default fill 0.9).

Partial-key bits are obtained by paper option **C.b**: sliced from the
record's full key via the record id (the base table is memory-resident in
the target systems, so the deref is a gather).  Point lookups can use the
partial-key screening path (`search_batch_partial`) which derefs only
entries whose partial window matches the query — the vectorized analogue of
Bohannon et al.'s sequential leaf procedure.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .dbits import (
    NO_DBIT,
    adjacent_dbit_positions,
    dbit_position_pairwise,
    lex_compare_le,
)
from .metadata import DSMeta
from .spans import span

__all__ = [
    "BTreeConfig",
    "BTree",
    "build_btree",
    "search_batch",
    "search_batch_partial",
    "lookup_batch_planned",
    "lookup_many_planned",
    "stack_trees",
    "tree_geometry",
    "NOT_FOUND_RID",
]

NODE_BYTES = 256
LEAF_HEADER = 24 + 8  # header + next-node pointer
NONLEAF_HEADER = 24
LEAF_ENTRY = 16
NONLEAF_ENTRY = 24
LEAF_MAX_FANOUT = (NODE_BYTES - LEAF_HEADER) // LEAF_ENTRY  # 14
NONLEAF_MAX_FANOUT = (NODE_BYTES - NONLEAF_HEADER) // NONLEAF_ENTRY  # 9


@dataclass(frozen=True)
class BTreeConfig:
    pk_bits: int = 16
    fill_factor: float = 0.9

    @property
    def leaf_cap(self) -> int:
        return max(2, int(LEAF_MAX_FANOUT * self.fill_factor))

    @property
    def nonleaf_cap(self) -> int:
        return max(2, int(NONLEAF_MAX_FANOUT * self.fill_factor))


@jax.tree_util.register_pytree_node_class
@dataclass
class BTree:
    """SoA partial-key B+tree.

    levels: root-first tuple of non-leaf levels, each a dict with
            child (m,c) int32 (-1 = empty), hi (m,c) int32 (index into the
            sorted key order), pk (m,c) uint32, dpos (m,c) int32,
            klen (m,c) int32.
    leaf:   dict with rid (L,c) uint32, pk (L,c) uint32, dpos (L,c) int32,
            klen (L,c) int32, valid (L,c) bool.
    sorted_full: (n, W) uint32 — full keys in sorted order (the "pointer to
            the highest index key" target; rows of the memory-resident table
            in key order).
    sorted_rids: (n,) uint32.
    """

    levels: tuple
    leaf: dict
    sorted_full: jnp.ndarray
    sorted_rids: jnp.ndarray
    n_keys: int
    config: BTreeConfig

    def tree_flatten(self):
        children = (self.levels, self.leaf, self.sorted_full, self.sorted_rids)
        aux = (self.n_keys, self.config)
        return children, aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        levels, leaf, sorted_full, sorted_rids = children
        return cls(levels, leaf, sorted_full, sorted_rids, *aux)

    @property
    def height(self) -> int:
        return len(self.levels) + 1

    def nodes_per_level(self) -> list[int]:
        return [int(l["child"].shape[0]) for l in self.levels] + [
            int(self.leaf["rid"].shape[0])
        ]

    def memory_bytes(self) -> int:
        return sum(self.nodes_per_level()) * NODE_BYTES


def _slice_bits(words: jnp.ndarray, start: jnp.ndarray, pk_bits: int) -> jnp.ndarray:
    """pk_bits bits of (m, W) keys starting at bit position start (m,)."""
    W = words.shape[-1]
    start = jnp.clip(start, 0, W * 32 - 1)
    wi = start // 32
    sh = (start % 32).astype(jnp.uint32)
    w0 = jnp.take_along_axis(words, wi[..., None], axis=-1)[..., 0]
    wi1 = jnp.minimum(wi + 1, W - 1)
    w1 = jnp.take_along_axis(words, wi1[..., None], axis=-1)[..., 0]
    w1 = jnp.where(wi + 1 < W, w1, 0)
    hi = w0 << sh
    lo = jnp.where(sh == 0, jnp.uint32(0), w1 >> (jnp.uint32(32) - sh))
    window = hi | lo
    return window >> jnp.uint32(32 - pk_bits)


def _np_pad(x: np.ndarray, rows: int, fill) -> np.ndarray:
    pad = rows - x.shape[0]
    if pad <= 0:
        return x
    return np.concatenate([x, np.full((pad,) + x.shape[1:], fill, dtype=x.dtype)])


def _leaf_program(cache, slice_fn, pk: int, donate: bool = False):
    """Stage-3 entry computation for the leaf level, one jitted program.

    All heavy per-entry work — the row gathers (sorted full keys, lengths,
    rids), the adjacent compressed-key D-bit positions mapped through
    D-offset, and the partial-key windows — fuses into a single compiled
    body over the bucket-padded shapes.  ``n`` and ``n_off`` arrive as
    dynamic scalar operands so every size inside the bucket replays the
    same program; padded lanes are clipped garbage, sliced off by the
    caller before assembly.

    ``donate`` donates the sort-permutation operand (``row_pad``, argnum
    4) — its information is fully absorbed into the gathers, so it is
    scratch after this program.  ``comp_pad``/``words_pad`` and the
    possibly-cached constants (lengths, rids) are never donated: the
    level programs and the caller still read them.
    """

    def prog(comp_pad, words_pad, lengths_pad, rids_pad, row_pad, d_off_pad, n, n_off):
        rowc = jnp.clip(row_pad, 0, jnp.maximum(n - 1, 0)).astype(jnp.int32)
        sorted_full = words_pad[rowc]
        klen = lengths_pad[rowc]
        rid_sorted = rids_pad[rowc]
        # distinction bit positions per sorted entry (entry 0 -> position 0)
        dpos_comp = adjacent_dbit_positions(comp_pad)
        safe = jnp.clip(dpos_comp, 0, n_off - 1)
        tail = jnp.where(dpos_comp == NO_DBIT, jnp.int32(0), d_off_pad[safe])
        dpos_full = jnp.concatenate(
            [jnp.zeros((1,), jnp.int32), tail.astype(jnp.int32)]
        )
        # partial key: pk bits following the distinction bit position
        # (option C.b: sliced from the record's full key)
        pkeys = slice_fn(sorted_full, dpos_full + 1, pk).astype(jnp.uint32)
        return sorted_full, klen, rid_sorted, dpos_full, pkeys

    return cache.jit(prog, **({"donate_argnums": (4,)} if donate else {}))


def _level_program(cache, slice_fn, pk: int, donate: bool = False):
    """Stage-3 entry computation for one non-leaf level, one jitted program.

    The adjacent highest-key D-bits (via compressed keys + D-offset, §5.3),
    the entry partial-key windows, and the key-length gather for a whole
    level run as one compiled body over bucket-padded node rows.

    ``donate`` donates the per-level hi-index operand (``hi_pad``, argnum
    0) — it is rebuilt host-side for every level, so the program may
    reuse its buffer.  The shared leaf outputs (``full_pad``/``klen_pad``)
    and ``comp_pad`` are read by every level and never donated.
    """

    def prog(hi_pad, comp_pad, full_pad, klen_pad, d_off_pad, n, n_off):
        hi_prev = jnp.concatenate([hi_pad[:1], hi_pad[:-1]])
        ac = jnp.clip(hi_prev, 0, n - 1)
        bc = jnp.clip(hi_pad, 0, n - 1)
        a = comp_pad[ac]
        b = comp_pad[bc]
        dc = dbit_position_pairwise(a, b)
        dfull = jnp.where(
            dc == NO_DBIT, jnp.int32(0), d_off_pad[jnp.clip(dc, 0, n_off - 1)]
        ).astype(jnp.int32)
        dfull = dfull.at[0].set(0)
        epk = slice_fn(full_pad[bc], dfull + 1, pk).astype(jnp.uint32)
        klen_hi = jnp.take(klen_pad, bc)
        return dfull, epk, klen_hi

    return cache.jit(prog, **({"donate_argnums": (0,)} if donate else {}))


def build_btree(
    comp_sorted: jnp.ndarray,
    row_sorted: jnp.ndarray,
    meta: DSMeta,
    table_words: jnp.ndarray,
    table_lengths: jnp.ndarray | None = None,
    config: BTreeConfig = BTreeConfig(),
    rids: jnp.ndarray | None = None,
    *,
    slice_fn=None,
    backend_name: str = "jnp",
    program_key_extra: tuple = (),
    cache=None,
    n_valid: int | None = None,
    donate: bool = False,
) -> BTree:
    """Bulk-build the tree from sorted compressed keys + row positions (§5.3).

    ``table_words`` is the base table's full keys by *row*; ``row_sorted``
    is the sort permutation over rows; ``rids`` (optional) maps rows to
    record ids stored in leaf entries (defaults to the row index).
    Distinction bit positions of entries come from adjacent *compressed*
    keys mapped through D-offset — no full-key comparisons are needed
    anywhere in the build, which is the point of the paper.

    Compiled-plan execution: each level's entry computation is one jitted
    program, cached in the shared plan cache (``repro.core.plancache``)
    under static ``(backend, bucket, n_words, leaf/nonleaf caps, pk)``;
    only cheap host-side reshapes happen between program calls.
    ``slice_fn`` lets a backend substitute its own partial-key window
    gather (the Pallas tiled kernel in ``repro.kernels.build``) — it must
    be bit-identical to ``_slice_bits``, and any configuration baked into
    the closure (tile size, interpret mode) must travel in
    ``program_key_extra`` so differently-configured backends never share a
    cached program.

    ``n_valid`` marks ``comp_sorted``/``row_sorted`` as already
    bucket-shaped with ``n_valid`` real rows (the pipeline's zero-copy
    chaining out of the sort stage: the sort's padded outputs feed the
    build programs directly).  Pad lanes may carry arbitrary content —
    sort sentinels or zeros — because every program gather clips to the
    dynamic ``n``/``n_off`` operands and the padded tail is sliced off
    before assembly; the pad-contents property test pins this down.

    ``donate=True`` donates the build programs' scratch operands — the
    sort permutation (``row_pad``) into the leaf program and the
    per-level hi-index buffer into each level program.  The caller must
    not read the (possibly identity-padded) ``row_sorted`` buffer again
    after the build; everything else the programs touch (``comp_sorted``,
    ``table_words``, the cached iota/const operands) is read-only and
    never donated.  The flag is part of the program cache keys, so
    donated and non-donated variants coexist.
    """
    from . import plancache

    cache = cache or plancache.get_cache()
    if slice_fn is None:
        slice_fn = _slice_bits
    donate = bool(donate) and plancache.donation_supported()

    n = int(comp_sorted.shape[0]) if n_valid is None else int(n_valid)
    W = int(table_words.shape[1])
    Wc = int(comp_sorted.shape[1])
    lc, nc = config.leaf_cap, config.nonleaf_cap
    pk = config.pk_bits

    d_off_np = np.asarray(meta.d_offset(), np.int32)
    n_off = int(d_off_np.shape[0])
    DB = W * 32  # d_off is padded to the max possible D-bit count (static)
    d_off_pad = jnp.asarray(_np_pad(d_off_np, DB, 0))

    B = (
        int(comp_sorted.shape[0])
        if n_valid is not None
        else plancache.bucket_for("build", n)
    )
    # pad_tail is identity on already-bucket-shaped inputs (the warm path)
    # and one dynamic_update_slice against a cached constant otherwise —
    # no per-call jnp.concatenate / jnp.full anywhere in the build
    comp_pad = plancache.pad_tail(jnp.asarray(comp_sorted, jnp.uint32), B, 0)
    words_pad = plancache.pad_tail(jnp.asarray(table_words, jnp.uint32), B, 0)
    row_pad = plancache.pad_tail(jnp.asarray(row_sorted, jnp.uint32), B, 0)
    if table_lengths is None:
        lengths_pad = plancache.const_full((B,), W * 4, jnp.int32)
    else:
        lengths_pad = plancache.pad_tail(jnp.asarray(table_lengths, jnp.int32), B, 0)
    rids_pad = (
        plancache.iota_u32(B)
        if rids is None
        else plancache.pad_tail(jnp.asarray(rids, jnp.uint32), B, 0)
    )

    # ---------------- leaf level (one cached program + host reshape) -------
    leaf_prog = cache.program(
        ("build_leaf", backend_name, B, W, Wc, pk, donate) + program_key_extra,
        lambda: _leaf_program(cache, slice_fn, pk, donate),
    )
    full_pad, klen_pad, rid_dev, dpos_dev, pkeys_dev = leaf_prog(
        comp_pad, words_pad, lengths_pad, rids_pad, row_pad, d_off_pad,
        np.int32(n), np.int32(n_off),
    )
    sorted_full = full_pad[:n]
    rid_sorted = rid_dev[:n]
    rid_np = np.asarray(rid_sorted)
    dpos_np = np.asarray(dpos_dev[:n])
    pkeys_np = np.asarray(pkeys_dev[:n])
    klen_np = np.asarray(klen_pad[:n])

    n_leaves = -(-n // lc)
    rows = n_leaves * lc
    leaf = {
        "rid": jnp.asarray(_np_pad(rid_np, rows, 0xFFFFFFFF).reshape(n_leaves, lc)),
        "pk": jnp.asarray(_np_pad(pkeys_np, rows, 0).reshape(n_leaves, lc)),
        "dpos": jnp.asarray(_np_pad(dpos_np, rows, 0).reshape(n_leaves, lc)),
        "klen": jnp.asarray(_np_pad(klen_np, rows, 0).reshape(n_leaves, lc)),
        "valid": jnp.asarray(np.arange(rows).reshape(n_leaves, lc) < n),
    }
    # highest (sorted-order) key index of each leaf
    child_hi = np.minimum(np.arange(n_leaves) * lc + lc, n).astype(np.int32) - 1

    # ---------------- non-leaf levels, bottom-up ----------------
    levels: list[dict] = []
    child_idx = np.arange(n_leaves, dtype=np.int32)
    while child_idx.shape[0] > 1:
        m_children = int(child_idx.shape[0])
        n_nodes = -(-m_children // nc)
        rows = n_nodes * nc
        Bn = plancache.bucket(rows)
        hi_np = _np_pad(child_hi.astype(np.int32), rows, -1)
        level_prog = cache.program(
            ("build_level", backend_name, Bn, B, W, Wc, pk, donate)
            + program_key_extra,
            lambda: _level_program(cache, slice_fn, pk, donate),
        )
        dfull_dev, epk_dev, klen_dev = level_prog(
            jnp.asarray(_np_pad(hi_np, Bn, -1)), comp_pad, full_pad, klen_pad,
            d_off_pad, np.int32(n), np.int32(n_off),
        )
        dfull = np.asarray(dfull_dev[:rows])
        epk = np.asarray(epk_dev[:rows])
        klen_hi = np.asarray(klen_dev[:rows])
        child_np = _np_pad(child_idx, rows, -1).reshape(n_nodes, nc)
        hi_grid = hi_np.reshape(n_nodes, nc)
        level = {
            "child": jnp.asarray(child_np),
            "hi": jnp.asarray(hi_grid),
            "pk": jnp.asarray(epk.astype(np.uint32).reshape(n_nodes, nc)),
            "dpos": jnp.asarray(dfull.astype(np.int32).reshape(n_nodes, nc)),
            "klen": jnp.asarray(klen_hi.reshape(n_nodes, nc)),
        }
        levels.append(level)
        # parents become the children of the next level up
        last_valid = (child_np >= 0).sum(axis=1) - 1
        child_hi = hi_grid[np.arange(n_nodes), last_valid]
        child_idx = np.arange(n_nodes, dtype=np.int32)

    levels.reverse()  # root first
    return BTree(
        levels=tuple(levels),
        leaf=leaf,
        sorted_full=sorted_full,
        sorted_rids=jnp.asarray(rid_np),
        n_keys=n,
        config=config,
    )


# ---------------------------------------------------------------------------
# batched search
# ---------------------------------------------------------------------------

def _first_ge(entry_keys: jnp.ndarray, valid: jnp.ndarray, query: jnp.ndarray) -> jnp.ndarray:
    """Index of first valid entry whose key >= query; last valid if none."""
    ge = lex_compare_le(query[:, None, :], entry_keys) & valid
    any_ge = jnp.any(ge, axis=1)
    first = jnp.argmax(ge, axis=1)
    last_valid = jnp.sum(valid.astype(jnp.int32), axis=1) - 1
    return jnp.where(any_ge, first, last_valid)


def _descend(tree: BTree, queries: jnp.ndarray) -> jnp.ndarray:
    """Non-leaf descent shared by every search path: (q,) leaf node ids.

    Each level compares the query against the entries' *highest index
    keys* through the highest-key pointer, exactly as the paper's search
    (§4.3) does — a full-key binary comparison per entry, vectorized over
    the node fanout and the query batch.
    """
    q = queries.shape[0]
    node = jnp.zeros((q,), jnp.int32)
    for level in tree.levels:
        hi = level["hi"][node]  # (q, c)
        valid = level["child"][node] >= 0
        hi_keys = tree.sorted_full[jnp.clip(hi, 0, tree.n_keys - 1)]  # (q, c, W)
        e = _first_ge(hi_keys, valid, queries)
        node = jnp.take_along_axis(level["child"][node], e[:, None], axis=1)[:, 0]
        node = jnp.maximum(node, 0)
    return node


def _leaf_keys(tree: BTree, node: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Full keys of each descended leaf's entry lanes: (pos0, (q, lc, W))."""
    lc = tree.config.leaf_cap
    pos0 = node * lc
    keys = tree.sorted_full[
        jnp.clip(pos0[:, None] + jnp.arange(lc)[None, :], 0, tree.n_keys - 1)
    ]
    return pos0, keys


@jax.jit
def search_batch(tree: BTree, queries: jnp.ndarray):
    """Vectorized descent; returns (found (q,), rid (q,), position (q,))."""
    node = _descend(tree, queries)
    rids = tree.leaf["rid"][node]  # (q, c)
    valid = tree.leaf["valid"][node]
    pos0, keys = _leaf_keys(tree, node)
    e = _first_ge(keys, valid, queries)
    key_at = jnp.take_along_axis(keys, e[:, None, None], axis=1)[:, 0, :]
    found = jnp.all(key_at == queries, axis=-1)
    rid = jnp.take_along_axis(rids, e[:, None], axis=1)[:, 0]
    return found, rid, pos0 + e


@jax.jit
def search_batch_partial(tree: BTree, queries: jnp.ndarray):
    """Point lookup via partial-key screening (vectorized Bohannon §4.3).

    For each leaf entry, a true match requires the query's ``pk``-bit window
    at the entry's distinction bit position to equal the entry's partial
    key.  Only screened candidates are dereferenced (full-key compare),
    which is the partial-key B-tree's cache saving; we report the deref
    count so benchmarks can measure it.
    """
    node = _descend(tree, queries)
    lc = tree.config.leaf_cap
    pk = tree.config.pk_bits
    dpos = tree.leaf["dpos"][node]  # (q, c)
    entry_pk = tree.leaf["pk"][node]
    valid = tree.leaf["valid"][node]
    # query window at each entry's dpos
    qwin = _slice_bits(queries[:, None, :].repeat(lc, 1), dpos + 1, pk)
    candidate = (qwin == entry_pk) & valid
    n_deref = jnp.sum(candidate.astype(jnp.int32), axis=1)
    # deref candidates only: compare full keys where candidate
    _, keys = _leaf_keys(tree, node)
    eq = jnp.all(keys == queries[:, None, :], axis=-1) & candidate
    found = jnp.any(eq, axis=1)
    e = jnp.argmax(eq, axis=1)
    rid = jnp.take_along_axis(tree.leaf["rid"][node], e[:, None], axis=1)[:, 0]
    return found, jnp.where(found, rid, jnp.uint32(0xFFFFFFFF)), n_deref


# ---------------------------------------------------------------------------
# the lookup backend op: plan-cached batched point lookup
# ---------------------------------------------------------------------------

#: rid every backend returns for a missing query — lookup results must be
#: byte-identical across backends, so the miss lane cannot carry whatever
#: neighbor entry the descent happened to land on
NOT_FOUND_RID = np.uint32(0xFFFFFFFF)


def _leaf_match_full(tree, node, keys, queries):
    """Default leaf probe: full-key equality over every entry lane."""
    del tree, node
    return jnp.all(keys == queries[:, None, :], axis=-1)


def _lookup_program(cache, leaf_match_fn):
    """The batched point-lookup body, one jitted program.

    The descent is ``search_batch``'s (highest-key compares per non-leaf
    level), but the leaf stage runs a substitutable ``leaf_match_fn(tree,
    node, keys, queries) -> (q, lc) bool`` — full-key equality on the jnp
    oracle, the partial-key probe kernel on pallas — and the miss lanes are
    normalized to ``NOT_FOUND_RID`` so outputs are byte-identical across
    backends.  Tree geometry (level shapes, ``n_keys``, config) is part of
    the jit signature: a snapshot of the same-sized index replays the
    program, a resized one re-traces exactly once (counted by the plan
    cache's ``traces``).
    """

    def prog(tree, queries, n_valid):
        # normalize pad lanes in-program: the host pads with a cached
        # constant whose content is irrelevant — lanes >= n_valid become
        # all-ones queries (harmless descents, sliced off by the caller)
        lane = jnp.arange(queries.shape[0], dtype=jnp.uint32)
        queries = jnp.where(
            (lane < n_valid)[:, None], queries, jnp.uint32(0xFFFFFFFF)
        )
        node = _descend(tree, queries)
        valid = tree.leaf["valid"][node]
        _, keys = _leaf_keys(tree, node)
        eq = leaf_match_fn(tree, node, keys, queries) & valid
        found = jnp.any(eq, axis=1)
        e = jnp.argmax(eq, axis=1)
        rid = jnp.take_along_axis(tree.leaf["rid"][node], e[:, None], axis=1)[:, 0]
        return found, jnp.where(found, rid, jnp.uint32(NOT_FOUND_RID))

    return cache.jit(prog)


def lookup_batch_planned(
    tree: BTree,
    queries: jnp.ndarray,
    *,
    backend_name: str = "jnp",
    leaf_match_fn=None,
    program_key_extra: tuple = (),
    cache=None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Batched point lookup through the shared plan cache (§4.3 search).

    Returns ``(found (q,) bool, rid (q,) uint32)`` with miss lanes
    normalized to :data:`NOT_FOUND_RID` — the backend ``lookup`` op's
    byte-identity contract.  The query batch pads to a plan-cache bucket
    (floor tunable via ``plancache.set_bucket_floor("lookup", ...)``)
    against a cached fill constant; the dynamic valid count travels as a
    program operand and the pad lanes are normalized to all-ones queries
    *inside* the program (their answers are garbage, sliced off before
    return), so a steady query stream at drifting batch sizes replays one
    compiled program per bucket with zero host-side pad allocation.
    ``leaf_match_fn`` substitutes
    the leaf probe (it must imply full-key equality bit-for-bit — see
    ``_lookup_program``); configuration baked into it travels in
    ``program_key_extra`` so differently-configured backends never share a
    cached program.
    """
    from . import plancache

    cache = cache or plancache.get_cache()
    if leaf_match_fn is None:
        leaf_match_fn = _leaf_match_full
    queries = jnp.asarray(queries, jnp.uint32)
    q, w = int(queries.shape[0]), int(queries.shape[1])
    b = plancache.bucket_for("lookup", q)
    with span("lookup"):
        prog = cache.program(
            ("lookup", backend_name, b, w) + program_key_extra,
            lambda: _lookup_program(cache, leaf_match_fn),
        )
        qp = plancache.pad_tail(queries, b, 0xFFFFFFFF)
        found, rid = prog(tree, qp, np.uint32(q))
        return found[:q], rid[:q]


# ---------------------------------------------------------------------------
# multi-tenant lookup: T same-geometry trees stacked, one program
# ---------------------------------------------------------------------------


def tree_geometry(tree: BTree) -> tuple:
    """Static shape signature of a tree — the arena bucketing key.

    Two trees with equal geometry can be stacked into one arena and
    replay one compiled ``lookup_many`` program; a rebuild that changes
    any array shape (or ``n_keys``, or the config) changes the geometry
    and must migrate to a different arena bucket.  The tuple is hashable
    and travels inside plan-cache keys.
    """
    levels = tuple(
        tuple(sorted((k, tuple(map(int, v.shape))) for k, v in level.items()))
        for level in tree.levels
    )
    leaf = tuple(sorted((k, tuple(map(int, v.shape))) for k, v in tree.leaf.items()))
    return (
        levels,
        leaf,
        tuple(map(int, tree.sorted_full.shape)),
        tuple(map(int, tree.sorted_rids.shape)),
        int(tree.n_keys),
        int(tree.config.pk_bits),
        float(tree.config.fill_factor),
    )


def stack_trees(trees, capacity: int | None = None) -> BTree:
    """Stack T same-geometry trees on a new leading tenant axis.

    Returns a :class:`BTree` whose every array leaf has shape
    ``(capacity,) + member_shape`` — a valid pytree over which
    ``jax.vmap`` runs the existing descent, which is how the jnp
    ``lookup_many`` oracle is built.  ``capacity`` defaults to the next
    power of two ``>= len(trees)`` so that tenants joining an arena
    within its capacity replay one compiled program; pad slots replicate
    the first member (their queries are masked out by ``n_valid``, so
    the content is irrelevant but must be shape-correct).
    """
    trees = list(trees)
    if not trees:
        raise ValueError("stack_trees needs at least one tree")
    geom = tree_geometry(trees[0])
    for i, t in enumerate(trees[1:], 1):
        if tree_geometry(t) != geom:
            raise ValueError(
                f"tree {i} geometry differs from tree 0; same-geometry "
                "trees only — bucket by tree_geometry() first"
            )
    t_live = len(trees)
    if capacity is None:
        capacity = 1 << max(0, (t_live - 1).bit_length())
    if capacity < t_live:
        raise ValueError(f"capacity {capacity} < {t_live} trees")
    padded = trees + [trees[0]] * (capacity - t_live)
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *padded)


def _leaf_match_many_full(tree, node, keys, queries):
    """Default stacked leaf probe: full-key equality, tenant-major.

    ``keys`` is (T, q, lc, W), ``queries`` (T, q, W) — the T-leading twin
    of :func:`_leaf_match_full`, same math per tenant slice.
    """
    del tree, node
    return jnp.all(keys == queries[:, :, None, :], axis=-1)


def _lookup_many_program(cache, leaf_match_many_fn):
    """The fused cross-tenant point-lookup body, one jitted program.

    The single-snapshot descent (`_descend`) is ``vmap``-ed over the
    stacked tree's tenant axis, so T tenants' query blocks answer in one
    dispatch of one compiled program — the multi-tenant fan-out the
    ROADMAP asks for.  Per-tenant valid counts arrive as a ``(T,)``
    operand; lanes at or past a tenant's count (including whole pad
    tenants in a partially filled arena) are normalized to all-ones
    queries in-program, exactly like the single path, so results are
    byte-identical per tenant to ``_lookup_program`` on that tenant's
    tree alone.  ``leaf_match_many_fn(tree, node, keys, queries) ->
    (T, q, lc) bool`` substitutes the leaf probe (tenant-major Pallas
    kernel on the pallas backend) and must imply full-key equality
    bit-for-bit.
    """

    return cache.jit(_lookup_many_body(leaf_match_many_fn))


def _lookup_many_body(leaf_match_many_fn):
    """The un-jitted fused lookup body — see :func:`_lookup_many_program`.

    Exposed separately so the distributed backend can wrap it in a
    ``shard_map`` over the tenant axis before handing it to the plan
    cache's jit.
    """

    def prog(tree, queries, n_valid):
        lane = jnp.arange(queries.shape[1], dtype=jnp.uint32)
        live = lane[None, :] < n_valid[:, None]  # (T, q)
        queries = jnp.where(live[..., None], queries, jnp.uint32(0xFFFFFFFF))
        node = jax.vmap(_descend)(tree, queries)  # (T, q)
        valid = jax.vmap(lambda t, n: t.leaf["valid"][n])(tree, node)
        keys = jax.vmap(lambda t, n: _leaf_keys(t, n)[1])(tree, node)
        eq = leaf_match_many_fn(tree, node, keys, queries) & valid
        found = jnp.any(eq, axis=2)
        e = jnp.argmax(eq, axis=2)
        rids = jax.vmap(lambda t, n: t.leaf["rid"][n])(tree, node)
        rid = jnp.take_along_axis(rids, e[..., None], axis=2)[..., 0]
        return found, jnp.where(found, rid, jnp.uint32(NOT_FOUND_RID))

    return prog


def lookup_many_planned(
    stacked: BTree,
    queries: jnp.ndarray,
    n_valid=None,
    *,
    backend_name: str = "jnp",
    leaf_match_many_fn=None,
    program_key_extra: tuple = (),
    cache=None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fused multi-tenant point lookup through the shared plan cache.

    ``stacked`` is a :func:`stack_trees` arena of T same-geometry
    snapshots; ``queries`` is ``(T_q, q, W)`` with ``T_q <= T`` — tenant
    ``t``'s block is answered against member tree ``t``.  ``n_valid``
    (optional, ``(T_q,)``) gives each tenant's live lane count; missing
    tenant rows up to the arena capacity are padded with zero-valid
    blocks, so a partially filled arena still replays the capacity-shaped
    program.  Returns ``(found (T_q, q) bool, rid (T_q, q) uint32)``,
    each tenant's slice byte-identical to :func:`lookup_batch_planned`
    on that tenant's tree alone (the lookup byte-identity contract,
    lifted over the tenant axis).

    The program cache key buckets on ``(T, query_bucket, tree
    geometry)`` per the zero-retrace discipline: tenants joining within
    capacity, query batches drifting within a bucket, and snapshot churn
    at fixed geometry all replay one compiled program (observable per op
    via ``PlanCache.stats()["per_op"]["lookup_many"]``).
    """
    from . import plancache

    cache = cache or plancache.get_cache()
    if leaf_match_many_fn is None:
        leaf_match_many_fn = _leaf_match_many_full
    queries = jnp.asarray(queries, jnp.uint32)
    if queries.ndim != 3:
        raise ValueError(f"queries must be (T, q, W), got {queries.shape}")
    t_q, q, w = (int(s) for s in queries.shape)
    t_cap = int(stacked.sorted_full.shape[0])
    if t_q > t_cap:
        raise ValueError(f"{t_q} tenant blocks > arena capacity {t_cap}")
    if n_valid is None:
        nv = np.full((t_q,), q, np.uint32)
    else:
        nv = np.asarray(n_valid, np.uint32).reshape(-1)
        if nv.shape[0] != t_q:
            raise ValueError(f"n_valid has {nv.shape[0]} rows, expected {t_q}")
    nv_full = np.zeros((t_cap,), np.uint32)
    nv_full[:t_q] = np.minimum(nv, q)
    b = plancache.bucket_for("lookup_many", q)
    with span("lookup"):
        prog = cache.program(
            ("lookup_many", backend_name, t_cap, b, w, tree_geometry(stacked))
            + program_key_extra,
            lambda: _lookup_many_program(cache, leaf_match_many_fn),
        )
        qp = plancache.pad_tail(queries, b, 0xFFFFFFFF, axis=1)
        qp = plancache.pad_tail(qp, t_cap, 0xFFFFFFFF, axis=0)
        found, rid = prog(stacked, qp, jnp.asarray(nv_full))
        return found[:t_q, :q], rid[:t_q, :q]
