"""Milliseconds a phase spends preparing and dispatching the fused combine
on the host: the program spans ``combine.collect`` (ready announcements to
the padded payload) and ``combine.dispatch`` (the enqueue; a recompile
shows here), summed over the profiler's window, per phase."""


def read(run):
    try:
        from repro.obs import profile_totals
    except ImportError:  # a program from before the spans: no metric
        return None
    spans = profile_totals()["spans"]
    names = ("combine.collect", "combine.dispatch")
    if any(n not in spans for n in names) or not run.phases:
        return None
    return sum(spans[n][1] for n in names) / run.phases * 1e3
