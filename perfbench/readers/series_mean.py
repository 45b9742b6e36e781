"""The mean of a series of numbers the runner recorded."""


def read(run, series):
    values = list(run.series.get(series, []))
    return sum(values) / len(values) if values else None
