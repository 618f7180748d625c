"""Bytes fetched from the device to the host per acknowledged operation: the
program counter ``d2h_bytes`` (the fetched arrays' ``nbytes``, an exact
count) over the profiler's window."""


def read(run):
    try:
        from repro.obs import profile_totals
    except ImportError:  # a program from before the counters: no metric
        return None
    counters = profile_totals()["counters"]
    if "d2h_bytes" not in counters or not run.acked_ops:
        return None
    return counters["d2h_bytes"] / run.acked_ops
