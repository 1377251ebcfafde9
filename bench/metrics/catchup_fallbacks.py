"""Share of the window's applies, in percent, that fell back to a full
rebuild: their ``timings`` hold no ``merge``, because the insert rule set
a distinction bit the standing run was not extracted under."""


def read(run):
    if not run.rebuilds:
        return None
    fell = sum("merge" not in r["timings"] for r in run.rebuilds)
    return 100.0 * fell / len(run.rebuilds)
