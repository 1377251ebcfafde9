"""Share of the traced window (closed-loop lookups) in which no operation
ran on the device, in percent."""

from bench import trace


def read(run):
    if not run.requests:
        return None
    return 100.0 * trace.idle_share(run.trace, run.window)
