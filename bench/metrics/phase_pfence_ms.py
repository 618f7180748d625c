"""Milliseconds of the phase's one pfence (slots and responses), per phase:
the program span ``pfence.phase`` over the profiler's window."""


def read(run):
    try:
        from repro.obs import profile_totals
    except ImportError:  # a program from before the spans: no metric
        return None
    spans = profile_totals()["spans"]
    if "pfence.phase" not in spans or not run.phases:
        return None
    return spans["pfence.phase"][1] / run.phases * 1e3
