"""Milliseconds the clients' announcements spend in their pfences (two per
announcement), summed per phase: the program span ``pfence.announce`` over
the profiler's window."""


def read(run):
    try:
        from repro.obs import profile_totals
    except ImportError:  # a program from before the spans: no metric
        return None
    spans = profile_totals()["spans"]
    if "pfence.announce" not in spans or not run.phases:
        return None
    return spans["pfence.announce"][1] / run.phases * 1e3
