"""Merge stage wall per incremental apply: the pipeline's own
``timings["merge"]`` (barriered, span ``repro.rebuild.merge``), averaged
over the applies that ran ``run_incremental`` to the end; a fallback to
the full rebuild has no merge."""


def read(run):
    walls = [r["timings"]["merge"] for r in run.rebuilds if "merge" in r["timings"]]
    return sum(walls) / len(walls) if walls else None
