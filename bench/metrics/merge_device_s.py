"""Device seconds of the merge stage per incremental apply: the union of
device operations from the first call into the backend's
``merge_sorted`` to the first call into its ``build`` (host spans
``bench.backend.merge_sorted`` and ``bench.backend.build`` inside each
``bench.apply``).  That is the ``merge_padded`` program over the
(2^24, 2^17) bucket pair, and the record-id gather dispatched after it;
the pipeline waits for the merge before the build starts.  Applies that
fell back to a full rebuild call no merge and are left out."""

from bench import trace


def merged_applies(run) -> trace.Trace:
    """``run.trace`` with only the ``bench.apply`` spans that hold a
    ``bench.backend.merge_sorted`` span."""
    spans = run.trace.spans
    merges = [s[1] for s in spans if s[0] == "bench.backend.merge_sorted"]
    keep = [s for s in spans
            if s[0] != "bench.apply" or any(s[1] <= t <= s[2] for t in merges)]
    return trace.Trace(ops=run.trace.ops, spans=keep)


def read(run):
    if not run.rebuilds:
        return None
    tr = merged_applies(run)
    if not any(s[0] == "bench.apply" for s in tr.spans):
        return None
    stages = trace.stage_busy_s(tr, run.window, "bench.apply",
                                "bench.backend.merge_sorted", "bench.backend.build")
    return sum(stages) / len(stages)
