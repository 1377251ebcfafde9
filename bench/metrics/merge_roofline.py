"""Share of the HBM roofline the merge stage reaches, in percent: the
least bytes a merge of the standing run and the delta moves, one read of
both runs and one write of the merged one, each row a compressed key and
a row id (``least_bytes``), over the merge stage's device seconds
(``merge_device_s``) times the chip's peak HBM bandwidth
(``bench/peaks.py``).  The bytes depend only on the data and its
D-bitmap, not on how the program merges."""

from bench.metrics import merge_device_s


def least_bytes(n_keys: int, comp_words: int) -> int:
    """Read n rows split over the two runs, write n merged rows."""
    return 2 * n_keys * (comp_words + 1) * 4


def read(run):
    seconds = merge_device_s.read(run)
    if seconds is None:
        return None
    return 100.0 * least_bytes(run.n_keys, run.comp_words) / (
        seconds * run.peaks["hbm_bytes_per_s"])
