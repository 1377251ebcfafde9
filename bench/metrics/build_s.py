"""Build stage wall per rebuild: the pipeline's own ``timings["build"]``,
barriered, averaged over the window's rebuilds."""


def read(run):
    walls = [r["timings"]["build"] for r in run.rebuilds if "build" in r["timings"]]
    return sum(walls) / len(walls) if walls else None
