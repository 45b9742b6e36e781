"""A percentile of a series of numbers the runner recorded."""

from perfbench import stats


def read(run, series, q):
    return stats.percentile(list(run.series.get(series, [])), q)
