"""pwbs per acknowledged operation in the window: every persistence write
of the announces, the slots, the responses and the epoch commits (exact
count, the paper's own)."""


def read(run):
    if not run.acked_ops:
        return None
    return run.counters.get("pwb", 0) / run.acked_ops
