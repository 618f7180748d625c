"""Milliseconds a phase spends serializing touched shards' slot leaves
(``np.save`` and the elision digest): the program span ``retire.serialize``,
summed over the profiler's window, per phase."""


def read(run):
    try:
        from repro.obs import profile_totals
    except ImportError:  # a program from before the spans: no metric
        return None
    spans = profile_totals()["spans"]
    if "retire.serialize" not in spans or not run.phases:
        return None
    return spans["retire.serialize"][1] / run.phases * 1e3
