"""Device milliseconds per client request: the device's busy time in the
window ÷ requests answered.  No rebuild runs in a read window, so every
device operation in it belongs to a lookup (the lookup program with its
probe kernel, and the copies into and out of it)."""

from bench import trace


def read(run):
    if not run.requests or run.rebuilds:
        return None
    return 1e3 * trace.busy_s(run.trace, run.window) / run.requests
