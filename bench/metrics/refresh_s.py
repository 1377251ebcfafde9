"""Refresh stage wall per rebuild: the pipeline's own
``timings["refresh_meta"]`` (span ``repro.rebuild.refresh``; the stage
returns host words, so its wall ends when the device work has), averaged
over the window's rebuilds or applies."""


def read(run):
    walls = [r["timings"]["refresh_meta"] for r in run.rebuilds
             if "refresh_meta" in r["timings"]]
    return sum(walls) / len(walls) if walls else None
