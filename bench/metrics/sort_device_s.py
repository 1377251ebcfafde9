"""Device seconds of the sort stage per rebuild: the union of device
operations from the first call into the backend's ``sort`` to the first
call into its ``build`` (host spans ``bench.backend.sort`` and
``bench.backend.build`` inside each ``bench.rebuild``).  Those are the
chunk sorts (``kernels/bitonic`` + ``lax.sort``) and the ``merge_padded``
ladder; the pipeline waits for the device before the build starts.  A
rebuild without those spans is an error, never 0."""

from bench import trace


def read(run):
    if not run.rebuilds:
        return None
    stages = trace.stage_busy_s(run.trace, run.window, "bench.rebuild",
                                "bench.backend.sort", "bench.backend.build")
    return sum(stages) / len(stages)
