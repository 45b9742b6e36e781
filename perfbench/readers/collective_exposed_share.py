"""Collectives' device time with no other operation running, over the traced
window. Nothing where the trace holds no collective at all."""

from perfbench import trace_reduce


def read(run):
    if run.trace is None or not trace_reduce.collective_events(run.trace):
        return None
    return 100.0 * trace_reduce.collective_exposed_s(run.trace) \
        / run.trace.window_s
