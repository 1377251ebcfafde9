"""Restart recovery: rebuilds run back to back from the window's start;
the one in flight when the time is up finishes and counts.  After each,
one ``probe`` request (a query spec, see ``bench/workload.py``) is
answered from the epoch it published.

``ready_s`` is the wall from the window's start to the end of the last
rebuild's probe over the rebuilds completed, so a stall between rebuilds
counts.  The comparison covers the last rebuild's outputs, every probe
answer, and the epochs published and read."""

from __future__ import annotations

import itertools
import time
import traceback

from bench import check, workload


def setup(system, table, meta, mix, rng):
    probes = workload.make_sets(table, rng, mix["probe"])
    epoch = system.rebuild(0)["epoch"]  # warms the rebuild; version 0
    system.lookup(probes[0])
    return {"table": table, "dbitmap": meta.dbitmap, "probes": probes,
            "setup_epoch": epoch}


def window(system, sess, seconds):
    win = workload.Window()
    probes = sess["probes"]
    with workload.annotate("bench.window"):
        win.start = time.perf_counter()
        for k in itertools.count():
            win.attempted += 1
            try:
                with workload.annotate("bench.rebuild"):
                    info = system.rebuild(k + 1)  # set-up built version 0
                    with workload.annotate("bench.probe"):
                        found, rid, epoch = system.lookup(probes[k % len(probes)])
            except Exception:  # counts as failed; no later rebuild is timed
                win.failures.append(traceback.format_exc())
                break
            done = time.perf_counter()
            info.update(done=done, version=k + 1, set=k % len(probes),
                        found=found, rid=rid, lookup_epoch=epoch)
            win.rebuilds.append(info)
            if done - win.start >= seconds:
                break
        win.end = time.perf_counter()
    win.state = system.state() if win.rebuilds else None
    return win


def values(sess, win):
    if not win.rebuilds:
        return {}
    return {"ready_s": (win.rebuilds[-1]["done"] - win.start) / len(win.rebuilds)}


def compare(ref, sess, win):
    table, setup_epoch = sess["table"], sess["setup_epoch"]
    out = [("requests_failed", len(win.failures), 0)]
    if not win.rebuilds:
        return out
    out += check.rebuild_state(ref, table, sess["dbitmap"], win.state,
                               win.rebuilds[-1]["version"])
    wrong = stale = 0
    for k, r in enumerate(win.rebuilds):
        found, rid = workload.expected(ref, table, sess["probes"][r["set"]],
                                       r["version"])
        wrong += int(((r["found"] != found) | (r["rid"] != rid)).sum())
        stale += int(r["lookup_epoch"] != r["epoch"])
    out += [
        ("rebuild_epochs_wrong", sum(r["epoch"] != setup_epoch + k + 1
                                     for k, r in enumerate(win.rebuilds)), 0),
        ("probe_answers_wrong", wrong, 0),
        ("probe_epochs_stale", stale, 0),
    ]
    return out
