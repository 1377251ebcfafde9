"""Traffic drivers, one module per ``driver`` a mix names.  Each defines:

``setup(system, table, meta, mix, rng) -> session``
    draw the mix's requests from ``rng`` and warm every shape the window
    will use (the harness has built ``system``; nothing else is built);
``window(system, session, seconds) -> workload.Window``
    drive ``system`` for ``seconds`` and wait for what is in flight; keep
    what the comparison needs in the window record;
``values(session, window) -> dict``
    the end-to-end numbers the window gives, by metric name;
``compare(ref, session, window) -> [(name, value, limit), ...]``
    the window's kept answers against ``ref``, a ``reference.SortedTable``
    of the table, after the window.

A driver may also define ``make_system(table, meta, pipeline_opts)`` where
its mix drives an operation ``systems.ProgramSystem`` does not offer.
"""
