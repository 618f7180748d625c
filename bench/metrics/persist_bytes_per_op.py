"""Bytes written to the persistence store per acknowledged operation in the
window: the sum of every pwb's length (exact count)."""


def read(run):
    if not run.acked_ops:
        return None
    return run.counters.get("pwb_bytes", 0) / run.acked_ops
