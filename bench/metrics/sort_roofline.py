"""Share of the HBM roofline the sort stage reaches, in percent: the
least bytes any sort of the run moves, one read and one write of every
compressed key and row id (2 * n * (w_comp + 1) * 4 bytes), over the
sort stage's device seconds (``sort_device_s``) times the chip's peak
HBM bandwidth (``bench/peaks.py``).  The bytes depend only on the data
and its D-bitmap, not on how the program sorts."""

from bench import trace


def read(run):
    if not run.rebuilds:
        return None
    stages = trace.stage_busy_s(run.trace, run.window, "bench.rebuild",
                                "bench.backend.sort", "bench.backend.build")
    seconds = sum(stages) / len(stages)
    least_bytes = 2 * run.n_keys * (run.comp_words + 1) * 4
    return 100.0 * least_bytes / (seconds * run.peaks["hbm_bytes_per_s"])
