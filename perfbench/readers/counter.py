"""A number the runner counted: `run.counters[name] * scale`."""


def read(run, name, scale=1.0):
    value = run.counters.get(name)
    return None if value is None else value * scale
