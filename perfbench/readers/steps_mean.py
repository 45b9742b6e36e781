"""The mean over the window's engine steps of a per-step reading
(`occupancy`, `utilization`), times `scale`."""


def read(run, field, scale=1.0):
    steps = run.series.get("steps", [])
    if not steps:
        return None
    return scale * sum(s[field] for s in steps) / len(steps)
