"""Sort stage wall per rebuild: the pipeline's own ``timings["sort"]``,
taken with a device barrier at each stage's end (the default synchronous
pipeline), averaged over the window's rebuilds."""


def read(run):
    walls = [r["timings"]["sort"] for r in run.rebuilds if "sort" in r["timings"]]
    return sum(walls) / len(walls) if walls else None
