"""Host spans at the layer boundaries of the rebuild and lookup paths.

``span("rebuild.sort")`` is a ``jax.profiler.TraceAnnotation`` named
``repro.rebuild.sort``, and ``spanned("rebuild")`` the same around each
call of a function.  A span writes into the profiler's own trace, on the
same clock as the device's events, and costs next to nothing when no
profiler runs.  A span adds no device barrier; where the work it covers
waits for the device, the span says so in its caller.

Span names (``repro.`` + name):

- ``rebuild``: the whole ``ReconstructionPipeline.run`` or
  ``run_incremental`` call, with ``rebuild.upload`` (the host's part of
  the host->device copy of the keys and the pad to the sort bucket),
  ``rebuild.extract``, ``.sort``, ``.build``, ``.refresh`` (the intervals
  of the matching ``timings`` entries), ``.filter`` and ``.merge``
  (``run_incremental`` only) and ``rebuild.stats`` inside it;
- ``snapshot.publish`` and ``snapshot.pin``: ``SnapshotCell.publish``
  and ``SnapshotCell.acquire``;
- ``lookup``: ``btree.lookup_batch_planned`` and ``lookup_many_planned``,
  from the program fetch to the return of the device arrays;
- ``stream.poll``: the whole ``StreamReplica.poll``, with
  ``stream.decode`` around each frame's unpack, CRC32C check and decode
  (their sum is the poll's ``decode`` seconds);
- ``replica.apply``: the whole ``Replica.apply``, with ``replica.fold``
  (the log replayed against the base rows) and ``replica.insert_rule``
  (the §4.3 metadata upkeep) inside it, the intervals of its ``fold`` and
  ``insert_rule`` timings, and the ``rebuild`` it runs.

Device time is attributed by program name instead: every plan-cache
program is named after its op family (``jit_sort``, ``jit_merge``, ...;
see ``PlanCache.jit``).
"""

from __future__ import annotations

import functools

import jax

PREFIX = "repro."


def span(name: str) -> jax.profiler.TraceAnnotation:
    """The host span ``repro.<name>``, as a context manager."""
    return jax.profiler.TraceAnnotation(PREFIX + name)


def spanned(name: str):
    """Decorator: each call of the function is the host span
    ``repro.<name>``."""
    return functools.partial(jax.profiler.annotate_function, name=PREFIX + name)
