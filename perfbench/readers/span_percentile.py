"""A percentile of the host-clock milliseconds of a `bench:` span, over the
spans that ended inside the measured window."""

from perfbench import stats


def read(run, span, q):
    inside = [ms for t, ms in run.series.get("span/" + span, [])
              if run.in_window(t)]
    return stats.percentile(inside, q)
