"""Share of the traced window (back-to-back change-log applies, each
probed) in which no operation ran on the device, in percent."""

from bench import trace


def read(run):
    if not run.rebuilds:
        return None
    return 100.0 * trace.idle_share(run.trace, run.window)
