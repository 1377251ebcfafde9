"""Shape-bucketed compiled-program cache for the reconstruction hot path.

The pipeline's data-parallel stages are shape-polymorphic in Python but
shape-*monomorphic* once compiled: every distinct ``(n, n_words)`` the
serving layer throws at a stage retraces and recompiles the program (the
ROADMAP's "jnp merge retraces per (na, nb)" open item is one instance; an
un-jitted build stage dispatching dozens of eager ops per level is the
worse one).  Under a churny workload the sizes drift every call and the
hot path never stops compiling.

This module fixes the program count, not the programs: inputs are padded
up to **bucket boundaries** (powers of two with a per-op floor), compiled
programs are memoized in a :class:`PlanCache` keyed by
``(op, backend, bucket(s), n_words, static config)``, and the dynamic
part of the shape travels as data — a ``n_valid`` scalar operand.  A
serving load whose sizes drift within a bucket replays one compiled
program forever; crossing a bucket boundary costs exactly one new compile.

Padding is an **in-program** concept: every cached program takes
bucket-shaped buffers plus the dynamic valid count, and the first thing
the traced body does is normalize the pad lanes with masked
``jnp.where`` writes over the static bucket shape.  The host side
therefore never materializes sentinel rows per call — the pad fill is a
**cached device constant** (built once per ``(shape, fill, dtype)``, on
the cold path only) that inputs are copied into with one
``lax.dynamic_update_slice``.  Warm same-bucket calls are shape-stable
replays with zero host allocation and zero eager ``jnp.concatenate`` /
``jnp.full`` dispatches — the property the warm-path regression test
asserts by monkeypatching those two functions.

Normalization discipline (what keeps byte-identity):

* **sort / merge / fused extract+sort** — pad lanes are rewritten to the
  all-ones sentinel key and row ids from a reserved range (``>= 2**31``,
  above any real row position, which the backend contract bounds by
  ``n < 2**31``).  Under the (key, row) determinism contract the pads
  therefore compare strictly after every real pair — equal-key ties break
  on the row id — so the first ``n`` output rows are bit-for-bit the
  unpadded result and the pads are sliced off before anything downstream
  sees them.  Because the normalization happens *inside* the program, the
  incoming pad lanes may carry arbitrary garbage.
* **build / refresh / lookup** — pads are inert garbage lanes: every
  consumer clips its gathers to the valid count (carried as a dynamic
  scalar operand) and the padded tail is sliced off host-side.

Counters: ``hits``/``misses`` count cache lookups; ``traces`` counts
actual program *tracings* (the Python body of a cached program runs only
while JAX traces it, so the counter increments exactly once per compile).
``assert cache.stats()["traces"]`` unchanged across a call is the strong
form of "zero recompilations" the regression tests use.

Under the in-process cache sits JAX's persistent compilation cache on
disk: :func:`enable_persistent_cache` places it, so a new process (a
restart, the next run of a script) loads compiled programs instead of
compiling them again.

Long-lived servers can bound the cache: ``PlanCache(max_programs=N)``
evicts the least-recently-used program past the bound (``evictions``
counts them; an evicted program that is needed again simply rebuilds and
re-traces).  ``auto_size=True`` additionally grows the bound when a
recent window of lookups shows a low hit rate *while* evictions occur —
the thrash signature of a bound set below the working set — doubling
``max_programs`` up to ``auto_size_cap``.  The default is unbounded.
"""

from __future__ import annotations

import math
import os
import threading
import time
import warnings
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "BUCKET_MIN",
    "ROW_PAD_A",
    "ROW_PAD_B",
    "bucket",
    "bucket_for",
    "set_bucket_floor",
    "get_bucket_floor",
    "PlanCache",
    "get_cache",
    "reset_cache",
    "set_max_programs",
    "cache_stats",
    "scoped_cache",
    "donation_supported",
    "enable_persistent_cache",
    "const_full",
    "iota_u32",
    "pad_tail",
    "pad_rows_2d",
    "pad_rows_1d",
    "pad_run",
    "sort_padded",
    "merge_padded",
    "fused_extract_sort_padded",
    "adjacent_dpos_padded",
    "ChunkPlan",
    "tune_chunking",
]

#: default bucket floor — tiny inputs share one program instead of one per
#: size; per-op overrides via :func:`set_bucket_floor`
BUCKET_MIN = 256

#: sentinel key word for pad rows (sorts last; ties break on the row id)
SENTINEL = np.uint32(0xFFFFFFFF)

#: pad row-id bases: above any real row position (the backend contract has
#: rows in [0, n) with n < 2**31) and distinct between the two merge runs
ROW_PAD_A = np.uint32(0x80000000)
ROW_PAD_B = np.uint32(0xC0000000)


def bucket(n: int, minimum: int = BUCKET_MIN) -> int:
    """Smallest power of two >= max(n, minimum)."""
    n = max(int(n), int(minimum))
    return 1 << (n - 1).bit_length()


#: per-op bucket floors (op -> floor); ops not listed use ``BUCKET_MIN``.
#: The knob exists because one floor does not fit every op: a lookup
#: query batch of 32 paying a 256-lane descent is pure wasted work, while
#: the sort floor below 256 would shatter the program cache for no win.
_FLOORS: dict[str, int] = {}


def set_bucket_floor(op: str, floor: int | None) -> None:
    """Override the bucket floor for one op family (``None`` restores the
    ``BUCKET_MIN`` default).  Lowering a floor after programs were traced
    at the old floor costs one re-trace per newly reachable bucket —
    change floors at startup, not mid-stream."""
    if floor is None:
        _FLOORS.pop(op, None)
        return
    if int(floor) < 1:
        raise ValueError(f"bucket floor must be >= 1, got {floor}")
    _FLOORS[op] = int(floor)


def get_bucket_floor(op: str) -> int:
    """The effective bucket floor for ``op``."""
    return _FLOORS.get(op, BUCKET_MIN)


def bucket_for(op: str, n: int) -> int:
    """Bucket of ``n`` under ``op``'s floor (see :func:`set_bucket_floor`)."""
    return bucket(n, get_bucket_floor(op))


@dataclass
class PlanCache:
    """Memoized compiled programs + hit/miss/trace/eviction counters.

    ``max_programs`` (optional) bounds the cache: past the bound the
    least-recently-used program is evicted (``programs`` is kept in
    recency order — a hit re-inserts its key at the end).

    ``auto_size=True`` turns on hit-rate-driven growth of the bound:
    whenever a window of ``auto_size_window`` lookups closes with a hit
    rate below ``auto_size_hit_rate`` *and* at least one eviction inside
    the window (i.e. the cache is thrashing, not merely cold), the bound
    doubles, capped at ``auto_size_cap``.  ``resizes`` counts the growth
    events (not part of :meth:`stats` — the zero-retrace assertions diff
    that dict exactly).

    The cache is thread-safe: lookups, inserts, LRU maintenance and
    every counter run under one re-entrant mutex, so N serving threads
    replaying warm programs concurrently with a rebuilding writer see
    exact ``hits``/``misses``/``traces`` counts (the concurrent
    zero-retrace assertions depend on that) and a racing cold miss
    builds each program exactly once — both racers get the *same*
    jitted callable, and JAX's own dispatch locking makes its first
    trace single-shot.  The compile itself (the first call of the
    returned program) happens outside the mutex.
    """

    programs: dict = field(default_factory=dict)
    hits: int = 0
    misses: int = 0
    traces: int = 0
    evictions: int = 0
    #: per-op-family counters keyed by the op name (``key[0]`` of every
    #: program key): op -> {"hits", "misses", "traces"}.  Surfaced through
    #: :meth:`stats` so benches and soaks can see *which* family retraced
    #: (e.g. the tenant-axis ``lookup_many`` bucketing) instead of only an
    #: aggregate trace delta.
    per_op: dict = field(default_factory=dict)
    _building_op: str | None = field(default=None, repr=False)
    max_programs: int | None = None
    auto_size: bool = False
    auto_size_cap: int = 4096
    auto_size_window: int = 64
    auto_size_hit_rate: float = 0.5
    resizes: int = 0
    _win_lookups: int = 0
    _win_hits: int = 0
    _win_evictions: int = 0
    _lock: threading.RLock = field(default_factory=threading.RLock, repr=False)

    def __post_init__(self) -> None:
        if self.max_programs is not None and int(self.max_programs) < 1:
            raise ValueError(
                f"max_programs must be >= 1 or None, got {self.max_programs}"
            )

    def program(self, key: tuple, builder: Callable[[], Callable]) -> Callable:
        """The compiled program for ``key``, building it on first use.

        Atomic under the cache mutex: concurrent lookups of the same
        cold key build it once and share the callable (``builder`` is
        cheap — it wraps, it does not compile)."""
        with self._lock:
            self._win_lookups += 1
            op_stats = self._per_op(key)
            prog = self.programs.get(key)
            if prog is not None:
                self.hits += 1
                self._win_hits += 1
                op_stats["hits"] += 1
                if self.max_programs is not None:
                    # refresh recency: dicts iterate in insertion order, so
                    # re-inserting makes the oldest entry the LRU victim
                    del self.programs[key]
                    self.programs[key] = prog
                self._maybe_grow()
                return prog
            self.misses += 1
            op_stats["misses"] += 1
            # builders wrap synchronously under the lock, so any cache.jit
            # they call attributes its future tracings to this op family
            prev_op, self._building_op = self._building_op, self._op_of(key)
            try:
                prog = builder()
            finally:
                self._building_op = prev_op
            self.programs[key] = prog
            if self.max_programs is not None:
                while len(self.programs) > int(self.max_programs):
                    victim = next(iter(self.programs))
                    del self.programs[victim]
                    self.evictions += 1
                    self._win_evictions += 1
            self._maybe_grow()
            return prog

    def _maybe_grow(self) -> None:
        """Close an auto-size window and grow the bound on thrash."""
        if not self.auto_size or self.max_programs is None:
            return
        if self._win_lookups < int(self.auto_size_window):
            return
        hit_rate = self._win_hits / max(self._win_lookups, 1)
        if self._win_evictions > 0 and hit_rate < float(self.auto_size_hit_rate):
            grown = min(int(self.max_programs) * 2, int(self.auto_size_cap))
            if grown > int(self.max_programs):
                self.max_programs = grown
                self.resizes += 1
        self._win_lookups = self._win_hits = self._win_evictions = 0

    @staticmethod
    def _op_of(key: tuple) -> str:
        """The op-family name of a program key (``key[0]`` by convention)."""
        return str(key[0]) if isinstance(key, tuple) and key else str(key)

    def _per_op(self, key_or_op) -> dict:
        """The per-op counter dict for a key/op (created on first touch);
        caller holds the lock."""
        op = key_or_op if isinstance(key_or_op, str) else self._op_of(key_or_op)
        entry = self.per_op.get(op)
        if entry is None:
            entry = self.per_op[op] = {"hits": 0, "misses": 0, "traces": 0}
        return entry

    def jit(self, fn: Callable, **jit_kwargs) -> Callable:
        """``jax.jit`` with trace counting: the wrapper body executes only
        while JAX traces, so ``traces`` counts compilations, not calls.
        When called from inside a :meth:`program` builder the tracings are
        also attributed to that program's op family in :attr:`per_op`
        (``"_unkeyed"`` otherwise).

        The program is named after its op family (``fn``'s own name when
        unkeyed), so the compiled module is ``jit_sort``, ``jit_merge``,
        ``jit_lookup``, ... in the lowered text and the device trace."""
        op = self._building_op or "_unkeyed"

        def traced(*args, **kwargs):
            with self._lock:  # exact trace counts under concurrent tracing
                self.traces += 1
                self._per_op(op)["traces"] += 1
            return fn(*args, **kwargs)

        traced.__name__ = traced.__qualname__ = (
            op if self._building_op else getattr(fn, "__name__", op))
        jitted = jax.jit(traced, **jit_kwargs)
        if not jit_kwargs.get("donate_argnums"):
            return jitted

        # Donation is an aliasing *offer*: operands whose shape matches an
        # output are reused in place (and deleted); the rest — e.g. a
        # ladder merge's half-size input runs, whose output is strictly
        # larger — can't alias, stay live, and XLA warns about them at
        # lowering.  That warning is expected for the cascade's programs,
        # so silence it for donated programs only.
        def quiet(*args, **kwargs):
            with warnings.catch_warnings():
                warnings.filterwarnings(
                    "ignore", message="Some donated buffers were not usable"
                )
                return jitted(*args, **kwargs)

        return quiet

    def stats(self) -> dict[str, Any]:
        """Counter snapshot: ``programs`` (cached), ``hits``/``misses``
        (cache lookups), ``traces`` (actual JAX tracings — the number that
        must stay flat across a warm same-bucket call), ``evictions``
        (LRU victims), the configured ``max_programs`` bound, and
        ``per_op`` — the same hit/miss/trace counters broken down by op
        family (``key[0]`` of the program keys; ``cache.jit`` calls made
        outside a program builder land under ``"_unkeyed"``)."""
        with self._lock:
            return {
                "programs": len(self.programs),
                "hits": self.hits,
                "misses": self.misses,
                "traces": self.traces,
                "evictions": self.evictions,
                "max_programs": self.max_programs,
                "per_op": {op: dict(c) for op, c in self.per_op.items()},
            }

    def reset(self) -> None:
        """Drop every cached program and zero the counters (tests); the
        ``max_programs`` bound and auto-size configuration survive."""
        with self._lock:
            self.programs.clear()
            self.hits = self.misses = self.traces = self.evictions = 0
            self.resizes = 0
            self.per_op.clear()
            self._win_lookups = self._win_hits = self._win_evictions = 0


_GLOBAL = PlanCache()


def get_cache() -> PlanCache:
    """The process-global cache every backend shares by default."""
    return _GLOBAL


def reset_cache() -> None:
    """Reset the process-global cache (see :meth:`PlanCache.reset`) and
    drop the cached pad-fill device constants."""
    _GLOBAL.reset()
    _CONSTS.clear()


def set_max_programs(max_programs: int | None) -> None:
    """Bound (or unbound, with ``None``) the process-global cache.

    ``max_programs`` must be >= 1 (the hot program itself must stay
    cached) or ``None``.  Takes effect on the next
    :meth:`PlanCache.program` insert; already cached programs are
    evicted lazily as new ones land.
    """
    if max_programs is not None and int(max_programs) < 1:
        raise ValueError(
            f"max_programs must be >= 1 or None, got {max_programs}"
        )
    _GLOBAL.max_programs = (
        None if max_programs is None else int(max_programs)
    )


def cache_stats() -> dict[str, Any]:
    """Counter snapshot of the process-global cache (see
    :meth:`PlanCache.stats`); the zero-retrace assertions diff this."""
    return _GLOBAL.stats()


@contextmanager
def scoped_cache(cache: PlanCache | None = None):
    """Temporarily swap the process-global cache for ``cache`` (default: a
    fresh one).  Calibration passes like :func:`tune_chunking` run inside
    this scope so their probe programs neither pollute the serving cache
    nor pre-compile the programs a cold-path benchmark is about to time.
    The cached pad constants (``_CONSTS``) stay shared — they are
    immutable device values, not compiled programs.  The swap is a
    process-global rebind: run calibration before starting serving
    threads, not concurrently with them."""
    global _GLOBAL
    prev, _GLOBAL = _GLOBAL, (cache if cache is not None else PlanCache())
    try:
        yield _GLOBAL
    finally:
        _GLOBAL = prev


_DONATION_SUPPORTED: bool | None = None


def donation_supported() -> bool:
    """Whether this backend actually consumes ``donate_argnums`` buffers.

    Probed once per process: a tiny jitted add with a donated operand
    either deletes its input (donation honoured — CPU and TPU do) or
    leaves it alive with a "donation not implemented" warning (some
    platforms).  The padded-op wrappers fold the *effective* flag into
    their cache keys, so on a non-donating platform ``donate=True`` maps
    to the ordinary program instead of caching a useless variant.
    """
    global _DONATION_SUPPORTED
    if _DONATION_SUPPORTED is None:
        try:
            x = jnp.zeros((8,), jnp.uint32)
            with warnings.catch_warnings():
                warnings.simplefilter("ignore")
                jax.jit(lambda v: v + 1, donate_argnums=(0,))(x).block_until_ready()
            _DONATION_SUPPORTED = bool(x.is_deleted())
        except Exception:
            _DONATION_SUPPORTED = False
    return _DONATION_SUPPORTED


def enable_persistent_cache(root: str | os.PathLike) -> str:
    """Place JAX's persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps the cache
    there and no other path is set here.  Otherwise the cache lives at the
    fixed path ``<root>/.jax_cache`` — never a temporary name, a process id
    or a time, since a directory that moves between runs never hits.
    Programs that compile in under a second are not worth a disk entry and
    are left out (JAX's default threshold).
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(os.path.abspath(root), ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


# ---------------------------------------------------------------------------
# padding helpers — cached fill constants + one dynamic_update_slice; no
# per-call jnp.concatenate / jnp.full on the warm path
# ---------------------------------------------------------------------------

#: (shape, dtype name, fill) -> committed device constant.  Bounded by the
#: set of distinct bucket shapes in flight — the same cardinality as the
#: program cache itself.  Cleared by :func:`reset_cache`.
_CONSTS: dict[tuple, jnp.ndarray] = {}


def const_full(shape: tuple, fill, dtype) -> jnp.ndarray:
    """A cached device constant of ``shape`` filled with ``fill``.

    Built with ``jnp.full`` exactly once per ``(shape, dtype, fill)`` —
    the cold path; warm callers get the committed array back.  Callers
    must treat it as immutable (every consumer copies out of it via
    ``dynamic_update_slice``, which is out-of-place).

    Values produced while JAX is *tracing* (the pad helpers also run
    inside traced program bodies, e.g. the kernel ops' tile pads) are
    tracers and must never enter the cache — they would leak out of
    their trace.  Tracer results are returned uncached; the constant
    commits the first time the helper runs eagerly.
    """
    dtype = jnp.dtype(dtype)
    key = (tuple(shape), dtype.name, int(fill))
    out = _CONSTS.get(key)
    if out is None:
        out = jnp.full(tuple(shape), fill, dtype)
        if not isinstance(out, jax.core.Tracer):
            _CONSTS[key] = out
    return out


def iota_u32(n: int) -> jnp.ndarray:
    """Cached ``arange(n)`` uint32 — the row-position operand of a freshly
    scanned table, shared across calls (lane i of a bucket-shaped buffer
    holds row i, which is exactly the iota's lane i).  Tracer results are
    never cached (see :func:`const_full`)."""
    key = ((int(n),), "uint32", -1)  # fill -1 never collides with const_full
    out = _CONSTS.get(key)
    if out is None:
        out = jnp.arange(int(n), dtype=jnp.uint32)
        if not isinstance(out, jax.core.Tracer):
            _CONSTS[key] = out
    return out


def pad_tail(x: jnp.ndarray, total: int, fill, axis: int = 0) -> jnp.ndarray:
    """Grow ``x`` to ``total`` along ``axis`` against a cached fill constant.

    Identity when ``x`` is already ``total`` long (the warm zero-copy
    case); otherwise one ``lax.dynamic_update_slice`` into the cached
    constant — no ``jnp.concatenate``, no per-call ``jnp.full``.  The
    pad content is ``fill``; cached programs that take a dynamic valid
    count normalize their pads in-program and do not depend on it.
    """
    x = jnp.asarray(x)
    n = int(x.shape[axis])
    total = int(total)
    if n == total:
        return x
    if n > total:
        raise ValueError(f"cannot pad {n} rows down to {total}")
    shape = list(x.shape)
    shape[axis] = total
    base = const_full(tuple(shape), fill, x.dtype)
    if n == 0:
        return base
    return jax.lax.dynamic_update_slice(base, x, (0,) * x.ndim)


def pad_rows_2d(x: jnp.ndarray, rows: int, fill) -> jnp.ndarray:
    """Pad the leading axis of (n, W) to ``rows`` with ``fill``."""
    return pad_tail(x, rows, fill, axis=0)


def pad_rows_1d(x: jnp.ndarray, rows: int, fill) -> jnp.ndarray:
    """Pad a (n,) vector to ``rows`` with ``fill`` (1-D twin of
    :func:`pad_rows_2d`)."""
    return pad_tail(x, rows, fill, axis=0)


def pad_run(
    keys: jnp.ndarray, rows: jnp.ndarray, b: int, row_base: np.uint32 = ROW_PAD_A
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Pad a (key, row) run to ``b`` rows with sentinel pairs that sort last.

    Pad lane ``i`` gets the all-ones key and row id ``row_base + i`` —
    the same values the in-program normalization writes, so eagerly
    padded runs and dynamically counted ones are interchangeable.
    """
    n = int(keys.shape[0])
    keys = jnp.asarray(keys, jnp.uint32)
    rows = jnp.asarray(rows, jnp.uint32)
    if n >= b:
        return keys, rows
    keys_p = pad_tail(keys, b, SENTINEL)
    pad_ids = jnp.uint32(row_base) + iota_u32(b)
    rows_p = jax.lax.dynamic_update_slice(pad_ids, rows, (0,))
    return keys_p, rows_p


def _mask_run(keys, rows, n_valid, row_base):
    """In-program pad normalization: lanes >= n_valid become (all-ones
    key, reserved row id) pairs that sort strictly last.  Runs inside the
    traced body, so the incoming pad lanes may be arbitrary garbage."""
    lane = jnp.arange(keys.shape[0], dtype=jnp.uint32)
    valid = lane < n_valid
    keys = jnp.where(valid[:, None], keys, jnp.uint32(SENTINEL))
    rows = jnp.where(valid, rows, jnp.uint32(row_base) + lane)
    return keys, rows


# ---------------------------------------------------------------------------
# bucketed stage wrappers — every program takes bucket-shaped buffers plus
# a dynamic n_valid operand (a np.uint32 scalar: fixed dtype, no retrace)
# ---------------------------------------------------------------------------

def sort_padded(
    keys: jnp.ndarray,
    rows: jnp.ndarray,
    *,
    backend: str = "jnp",
    impl: Callable | None = None,
    extra_key: tuple = (),
    cache: PlanCache | None = None,
    n_valid: int | None = None,
    keep_padded: bool = False,
    donate: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Bucketed keyed sort: one compiled program per (backend, bucket, W).

    ``impl(keys_pad, rows_pad) -> (keys_sorted, rows_sorted)`` is the
    backend's sort body (default: the jnp keyed sort); it runs inside one
    jitted, cached program over the padded shapes, after the in-program
    pad normalization.  ``n_valid`` (optional) marks the inputs as
    already bucket-shaped with ``n_valid`` real rows — the zero-copy warm
    path; without it the inputs are padded here (one
    ``dynamic_update_slice`` against a cached constant).  ``keep_padded``
    returns the full bucket-shaped outputs (pads sorted to the tail) for
    callers that chain into another bucket-shaped stage.

    ``donate=True`` donates the *keys* operand to the compiled program
    (``donate_argnums``): XLA reuses its buffer for the output and the
    caller's array is consumed (``.is_deleted()``).  The rows operand is
    never donated — it is frequently the shared cached iota constant.
    Only donate buffers no other consumer will touch again.  The
    effective flag is part of the cache key, so donated and non-donated
    variants coexist; on platforms without donation support it degrades
    to the ordinary program (see :func:`donation_supported`).
    """
    cache = cache or _GLOBAL
    w = int(keys.shape[1])
    if n_valid is None:
        n = int(keys.shape[0])
        b = bucket_for("sort", n)
        keys = pad_tail(jnp.asarray(keys, jnp.uint32), b, SENTINEL)
        rows = pad_tail(jnp.asarray(rows, jnp.uint32), b, 0)
    else:
        n = int(n_valid)
        b = int(keys.shape[0])
    if impl is None:
        from .dbits import sort_words_keyed

        impl = sort_words_keyed

    don = bool(donate) and donation_supported()
    jit_kwargs = {"donate_argnums": (0,)} if don else {}

    def builder():
        def prog(kp, rp, nv):
            kp, rp = _mask_run(kp, rp, nv, ROW_PAD_A)
            return impl(kp, rp)

        return cache.jit(prog, **jit_kwargs)

    prog = cache.program(("sort", backend, b, w, don) + extra_key, builder)
    ks, rs = prog(keys, rows, np.uint32(n))
    if keep_padded:
        return ks, rs
    return ks[:n], rs[:n]


def merge_padded(
    keys_a: jnp.ndarray,
    rows_a: jnp.ndarray,
    keys_b: jnp.ndarray,
    rows_b: jnp.ndarray,
    *,
    backend: str = "jnp",
    cache: PlanCache | None = None,
    n_valid_a: int | None = None,
    n_valid_b: int | None = None,
    keep_padded: bool = False,
    donate: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Bucketed two-run merge: one program per (backend, bucket_a, bucket_b, W).

    Fixes the per-``(na, nb)`` retrace of the jnp merge (ROADMAP): any
    (na, nb) inside the same bucket pair replays the cached program.  Pad
    lanes are normalized *inside* the program (sentinel key, reserved row
    range, distinct between the runs), so the first ``na + nb`` merged
    rows are byte-identical to the unpadded merge regardless of what the
    incoming pad lanes carried.

    ``keep_padded`` returns the full ``(ba + bb,)``-shaped outputs (pads
    sorted strictly to the tail) for cascade callers that chain the run
    into another padded merge with ``n_valid``.  ``donate=True`` offers
    all four run operands to XLA for in-place reuse — the merge is their
    last reader.  Whether a buffer is actually consumed is up to the
    aliaser (an operand strictly smaller than every output, like an
    equal-halves merge input, can't alias and stays live until its
    Python reference drops).  Never pass arrays you (or a cached
    constant) still need; the effective flag is part of the cache key
    (see :func:`sort_padded`).
    """
    cache = cache or _GLOBAL
    w = int(keys_a.shape[1])
    if n_valid_a is None:
        na = int(keys_a.shape[0])
        ba = bucket_for("merge", na)
        keys_a = pad_tail(jnp.asarray(keys_a, jnp.uint32), ba, SENTINEL)
        rows_a = pad_tail(jnp.asarray(rows_a, jnp.uint32), ba, 0)
    else:
        na, ba = int(n_valid_a), int(keys_a.shape[0])
    if n_valid_b is None:
        nb = int(keys_b.shape[0])
        bb = bucket_for("merge", nb)
        keys_b = pad_tail(jnp.asarray(keys_b, jnp.uint32), bb, SENTINEL)
        rows_b = pad_tail(jnp.asarray(rows_b, jnp.uint32), bb, 0)
    else:
        nb, bb = int(n_valid_b), int(keys_b.shape[0])
    from .dbits import merge_words_keyed

    don = bool(donate) and donation_supported()
    jit_kwargs = {"donate_argnums": (0, 1, 2, 3)} if don else {}

    def builder():
        def prog(ka, ra, kb, rb, nva, nvb):
            ka, ra = _mask_run(ka, ra, nva, ROW_PAD_A)
            kb, rb = _mask_run(kb, rb, nvb, ROW_PAD_B)
            return merge_words_keyed(ka, ra, kb, rb)

        return cache.jit(prog, **jit_kwargs)

    prog = cache.program(("merge", backend, ba, bb, w, don), builder)
    km, rm = prog(keys_a, rows_a, keys_b, rows_b, np.uint32(na), np.uint32(nb))
    if keep_padded:
        return km, rm
    return km[: na + nb], rm[: na + nb]


def fused_extract_sort_padded(
    words: jnp.ndarray,
    plan,
    rows: jnp.ndarray,
    *,
    backend: str = "jnp",
    cache: PlanCache | None = None,
    n_valid: int | None = None,
    keep_padded: bool = False,
    donate: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Bucketed fused extract+sort (one program per bucket *and* plan).

    All-ones pad keys extract to the all-ones compressed pattern — the
    maximum any real key can compress to, since the slack bits of the last
    compressed word are zero for every key — and the reserved row range
    breaks the tie, so pads still sort strictly last.  The pads are
    normalized in-program from the dynamic valid count.

    ``donate=True`` donates the *words* operand (the rows operand is
    often the shared cached iota and is never donated).  Only safe when
    nothing downstream reads the full-key buffer again — the pipeline's
    full path keeps it alive for the build stage and must not donate.
    """
    cache = cache or _GLOBAL
    w = int(words.shape[1])
    if n_valid is None:
        n = int(words.shape[0])
        b = bucket_for("sort", n)
        words = pad_tail(jnp.asarray(words, jnp.uint32), b, SENTINEL)
        rows = pad_tail(jnp.asarray(rows, jnp.uint32), b, 0)
    else:
        n = int(n_valid)
        b = int(words.shape[0])

    don = bool(donate) and donation_supported()
    jit_kwargs = {"donate_argnums": (0,)} if don else {}

    def builder():
        from .compress import extract_bits
        from .dbits import sort_words_keyed

        def prog(wp, rp, nv):
            wp, rp = _mask_run(wp, rp, nv, ROW_PAD_A)
            return sort_words_keyed(extract_bits(wp, plan), rp)

        return cache.jit(prog, **jit_kwargs)

    prog = cache.program(("fused", backend, b, w, plan, don), builder)
    ks, rs = prog(words, rows, np.uint32(n))
    if keep_padded:
        return ks, rs
    return ks[:n], rs[:n]


def adjacent_dpos_padded(
    comp_sorted: jnp.ndarray,
    *,
    backend: str = "jnp",
    cache: PlanCache | None = None,
    n_valid: int | None = None,
    donate: bool = False,
) -> np.ndarray:
    """Adjacent distinction-bit positions of a sorted run, bucketed.

    The refresh stage's device half: one cached program per (backend,
    bucket, Wc) computes all bucket-1 adjacent D-bit positions over
    in-program-normalized lanes (pads become all-ones rows, whose
    adjacencies land past the ``n - 1`` slice); the host half (the
    scatter-OR into the 32-bit bitmap words) lives in
    ``repro.core.metadata.meta_on_rebuild``.  Returns (n-1,) int32 with
    ``NO_DBIT`` at equal-key adjacencies.

    ``donate=True`` donates the sorted-run operand — refresh is the last
    consumer of the padded sorted keys in the full pipeline, so its
    scratch is reclaimed in place.  Only pass buffers nothing else reads
    afterwards.
    """
    cache = cache or _GLOBAL
    wc = int(comp_sorted.shape[1])
    if n_valid is None:
        n = int(comp_sorted.shape[0])
        if n < 2:
            return np.zeros((0,), np.int32)
        b = bucket_for("refresh", n)
        comp_sorted = pad_tail(jnp.asarray(comp_sorted, jnp.uint32), b, SENTINEL)
    else:
        n = int(n_valid)
        if n < 2:
            return np.zeros((0,), np.int32)
        b = int(comp_sorted.shape[0])

    don = bool(donate) and donation_supported()
    jit_kwargs = {"donate_argnums": (0,)} if don else {}

    def builder():
        from .dbits import adjacent_dbit_positions

        def prog(cp, nv):
            lane = jnp.arange(cp.shape[0], dtype=jnp.uint32)
            cp = jnp.where((lane < nv)[:, None], cp, jnp.uint32(SENTINEL))
            return adjacent_dbit_positions(cp)

        return cache.jit(prog, **jit_kwargs)

    prog = cache.program(("refresh_dpos", backend, b, wc, don), builder)
    return np.asarray(prog(comp_sorted, np.uint32(n))[: n - 1], np.int32)


# ---------------------------------------------------------------------------
# measured chunk auto-tuning — closes the ROADMAP "chunk-size auto-tuning"
# item: chunk_threshold / chunk_size picked from measured per-bucket sort
# and merge program costs instead of static constructor knobs
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ChunkPlan:
    """A measured chunking policy for one backend.

    ``chunk_size`` minimizes the modeled *warm* cascade wall at ``ref_n``
    keys; ``chunk_threshold`` is the smallest power-of-two key count at
    which the chunked path's cold cost (compiles + cascade) undercuts the
    extrapolated monolithic sort's compile, i.e. the point where paying
    the cascade's extra warm work buys back more compile time than it
    costs.  The raw per-candidate samples ride along for transparency
    (seconds; ``*_cold`` includes the compile, ``*_warm`` is a replay).
    """

    backend: str
    chunk_size: int
    chunk_threshold: int
    ref_n: int
    n_words: int
    sort_cold: dict[int, float]
    sort_warm: dict[int, float]
    merge_cold: dict[int, float]
    merge_warm: dict[int, float]


def _cascade_warm_model(n: int, c: int, sort_w: float, merge_w: float) -> float:
    """Modeled warm cascade wall: per-chunk sorts + per-level merges.

    The merge sample is one equal-halves merge at output bucket ``2c``;
    higher levels scale linearly in merged rows times the rank search's
    log(bucket) growth.
    """
    n_chunks = -(-n // c)
    cost = n_chunks * sort_w
    per_row = merge_w / (2 * c)
    base_steps = max(math.log2(c), 1.0)
    runs, size = n_chunks, c
    while runs > 1:
        merged_rows = (runs // 2) * 2 * size
        cost += per_row * merged_rows * (max(math.log2(size), 1.0) / base_steps)
        runs = -(-runs // 2)
        size *= 2
    return cost


def _median_wall(fn, iters: int) -> float:
    walls = []
    for _ in range(max(int(iters), 1)):
        t0 = time.perf_counter()
        out = fn()
        jax.tree_util.tree_map(
            lambda x: x.block_until_ready()
            if hasattr(x, "block_until_ready") else x,
            out,
        )
        walls.append(time.perf_counter() - t0)
    walls.sort()
    return walls[len(walls) // 2]


def tune_chunking(
    backend,
    *,
    candidates: tuple[int, ...] = (1 << 16, 1 << 17, 1 << 18),
    n_words: int = 2,
    ref_n: int = 1 << 20,
    iters: int = 1,
    seed: int = 0,
) -> ChunkPlan:
    """Calibrate ``chunk_size`` / ``chunk_threshold`` for one backend.

    For every candidate chunk bucket ``c`` this times the backend's sort
    program at bucket ``c`` (cold = compile + run, then warm replays) and
    one equal-halves merge at output bucket ``2c``, all inside a
    :func:`scoped_cache` so the probe programs never enter — or
    pre-compile — the serving cache.  ``backend`` is duck-typed: anything
    with the ``sort`` / ``merge_sorted`` backend-op signatures works, so
    this module needs no import of ``repro.backends``.

    * ``chunk_size`` — the candidate minimizing the modeled warm cascade
      wall at ``ref_n`` keys (chunk sorts + log-depth merge levels; see
      ``_cascade_warm_model``).
    * ``chunk_threshold`` — chunking exists to bound *compile* cost and
      peak memory, not to beat the monolithic program's warm wall (a
      cascade always does ~log extra passes).  The threshold is the
      smallest power of two ``N >= 2 * chunk_size`` where the
      extrapolated monolithic cold cost (compile fitted as a power law
      over the two largest candidates + n·log n warm scaling) exceeds
      the chunked path's cold cost; if the model never crosses below
      ``ref_n`` the threshold falls back to ``ref_n``.
    """
    rng = np.random.default_rng(seed)
    cands = sorted(int(c) for c in candidates)
    if len(cands) < 2:
        raise ValueError("need at least two chunk-size candidates")
    for c in cands:
        if c & (c - 1):
            raise ValueError(f"chunk-size candidates must be powers of two: {c}")

    sort_cold: dict[int, float] = {}
    sort_warm: dict[int, float] = {}
    merge_cold: dict[int, float] = {}
    merge_warm: dict[int, float] = {}

    with scoped_cache():
        for c in cands:
            keys = jnp.asarray(
                rng.integers(0, 2**32, size=(c, n_words), dtype=np.uint32)
            )
            rows = iota_u32(c)
            sort_cold[c] = _median_wall(
                lambda: backend.sort(keys, rows, n_valid=c, keep_padded=True), 1
            )
            sort_warm[c] = _median_wall(
                lambda: backend.sort(keys, rows, n_valid=c, keep_padded=True),
                iters,
            )
            # equal-halves merge at output bucket 2c: two independently
            # sorted c-runs with disjoint row ranges (the cascade invariant)
            h = c // 2
            ka, ra = backend.sort(keys[:h], iota_u32(h), n_valid=h,
                                  keep_padded=True)
            kb, rb = backend.sort(keys[h:], iota_u32(h), n_valid=h,
                                  keep_padded=True)
            rb = rb + jnp.uint32(h)
            merge_cold[c] = _median_wall(
                lambda: backend.merge_sorted(
                    ka, ra, kb, rb, n_valid_a=h, n_valid_b=h, keep_padded=True
                ),
                1,
            )
            merge_warm[c] = _median_wall(
                lambda: backend.merge_sorted(
                    ka, ra, kb, rb, n_valid_a=h, n_valid_b=h, keep_padded=True
                ),
                iters,
            )

    chunk_size = min(
        cands,
        key=lambda c: _cascade_warm_model(
            ref_n, c, sort_warm[c], merge_warm[c]
        ),
    )

    # -- threshold: where the monolithic compile stops being worth paying --
    c1, c2 = cands[-2], cands[-1]
    comp1 = max(sort_cold[c1] - sort_warm[c1], 1e-6)
    comp2 = max(sort_cold[c2] - sort_warm[c2], 1e-6)
    # compile-cost growth exponent, clamped to a sane superlinear band
    alpha = math.log(comp2 / comp1) / math.log(c2 / c1)
    alpha = min(max(alpha, 1.0), 3.0)
    c_ref = chunk_size
    sort_compile = max(sort_cold[c_ref] - sort_warm[c_ref], 1e-6)
    merge_compile = max(merge_cold[c_ref] - merge_warm[c_ref], 1e-6)
    warm_rate = sort_warm[c2] / (c2 * max(math.log2(c2), 1.0))

    def mono_cold(n: int) -> float:
        return comp2 * (n / c2) ** alpha + warm_rate * n * math.log2(n)

    def chunked_cold(n: int) -> float:
        levels = max(math.ceil(math.log2(-(-n // c_ref))), 1)
        compiles = sort_compile + sum(
            merge_compile * (2**lvl) ** (alpha - 1.0) for lvl in range(levels)
        )
        return compiles + _cascade_warm_model(
            n, c_ref, sort_warm[c_ref], merge_warm[c_ref]
        )

    threshold = ref_n
    n = 2 * chunk_size
    while n < ref_n:
        if chunked_cold(n) < mono_cold(n):
            threshold = n
            break
        n *= 2

    return ChunkPlan(
        backend=getattr(backend, "name", "?"),
        chunk_size=chunk_size,
        chunk_threshold=threshold,
        ref_n=int(ref_n),
        n_words=int(n_words),
        sort_cold=sort_cold,
        sort_warm=sort_warm,
        merge_cold=merge_cold,
        merge_warm=merge_warm,
    )
