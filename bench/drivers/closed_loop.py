"""Read-only serving: one published snapshot, built in set-up, and
``clients`` closed-loop client threads that each send one ``request``
(a query spec, see ``bench/workload.py``) at a time for the whole window,
answered from a pinned epoch.

``lookups_per_s`` is the keys answered over the window's wall;
``lookup_p99_ms`` the 99th percentile of every request's time from its
send to its answer on the host.  The comparison covers every answer of
the window and the epoch it was read from."""

from __future__ import annotations

import itertools
import threading
import time
import traceback

import numpy as np

from bench import workload


def setup(system, table, meta, mix, rng):
    del meta
    pool = workload.make_sets(table, rng, mix["request"])
    epoch = system.rebuild(0)["epoch"]
    system.lookup(pool[0])  # the one request shape of the window
    return {"table": table, "pool": pool, "clients": int(mix["clients"]),
            "setup_epoch": epoch}


def window(system, sess, seconds):
    win = workload.Window()
    pool = sess["pool"]
    stop = threading.Event()
    counter = itertools.count()
    lock = threading.Lock()

    def client() -> None:
        while not stop.is_set():
            j = next(counter) % len(pool)
            t0 = time.perf_counter()
            try:
                with workload.annotate("bench.lookup"):
                    found, rid, epoch = system.lookup(pool[j])
            except Exception:  # a failed request counts, the client goes on
                with lock:
                    win.failures.append(traceback.format_exc())
                continue
            dt = time.perf_counter() - t0
            answer = workload.digest(found, rid)
            with lock:
                win.requests.append((j, dt, answer, epoch))

    threads = [threading.Thread(target=client, name=f"client{i}", daemon=True)
               for i in range(sess["clients"])]
    with workload.annotate("bench.window"):
        win.start = time.perf_counter()
        for t in threads:
            t.start()
        stop.wait(seconds)
        stop.set()
        for t in threads:
            t.join()
        win.end = time.perf_counter()
    win.attempted = next(counter)  # requests the clients started
    return win


def values(sess, win):
    if not win.requests:
        return {}
    lat = np.array([r[1] for r in win.requests])
    keys = len(win.requests) * sess["pool"].shape[1]
    return {"lookups_per_s": keys / (win.end - win.start),
            "lookup_p99_ms": float(np.percentile(lat, 99) * 1e3)}


def compare(ref, sess, win):
    out = [("requests_failed", len(win.failures), 0)]
    if not win.requests:
        return out
    found, rid = workload.expected(ref, sess["table"], sess["pool"], 0)
    wrong = sum(got != workload.digest(found[j], rid[j])
                for j, _, got, _ in win.requests)
    stale = sum(r[3] != sess["setup_epoch"] for r in win.requests)
    return out + [("requests_wrong", int(wrong), 0),
                  ("request_epochs_stale", int(stale), 0)]
