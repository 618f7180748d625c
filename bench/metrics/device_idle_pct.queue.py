"""Device idle share (%) of the traced window: 1 - busy / window, where busy
is the union of the intervals in which an operation ran on the device."""


def read(run):
    if run.trace is None or run.trace["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])
