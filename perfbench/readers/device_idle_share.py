"""1 - the union of device operation intervals over the traced window."""

from perfbench import trace_reduce


def read(run):
    if run.trace is None or not run.trace.ops:
        return None
    return 100.0 * trace_reduce.idle_share(run.trace)
