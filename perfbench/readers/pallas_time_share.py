"""Device time of the repo's Pallas kernels (`apex_*`) over busy time."""

from perfbench import trace_reduce


def read(run):
    if run.trace is None:
        return None
    busy = trace_reduce.busy_s(run.trace)
    return 100.0 * trace_reduce.prefix_seconds(run.trace, "apex_") / busy \
        if busy else None
