"""pfences per acknowledged operation in the window: every persistence
fence of the announces, the phases and the epoch commits (exact count)."""


def read(run):
    if not run.acked_ops:
        return None
    return run.counters.get("pfence", 0) / run.acked_ops
