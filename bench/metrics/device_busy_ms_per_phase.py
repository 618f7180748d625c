"""Device milliseconds per phase: the union of the intervals in which an
operation ran on the device, over the traced window, per phase."""


def read(run):
    if run.trace is None or not run.phases or run.trace["busy_s"] <= 0:
        return None
    return run.trace["busy_s"] / run.phases * 1e3
