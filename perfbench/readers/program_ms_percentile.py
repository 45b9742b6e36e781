"""A percentile of the device milliseconds of whole programs by name, from
the "XLA Modules" line of the trace."""

from perfbench import stats, trace_reduce


def read(run, program, q):
    if run.trace is None:
        return None
    ms = [s * 1e3 for _, s in trace_reduce.module_events(run.trace, program)]
    return stats.percentile(ms, q)
