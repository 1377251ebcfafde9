"""Change-log fold per apply: the mean of ``Replica.apply``'s own
``timings["fold"]`` (span ``repro.replica.fold``: the log replayed against
the base rows on the host) over the window's applies."""


def read(run):
    walls = [r["timings"]["fold"] for r in run.rebuilds if "fold" in r["timings"]]
    return sum(walls) / len(walls) if walls else None
