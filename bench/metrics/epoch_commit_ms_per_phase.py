"""Milliseconds a phase spends in the touched shards' two-increment epoch
commits (each an odd write, an fsync and an even write): the program span
``retire.epoch_commit``, summed over the profiler's window, per phase."""


def read(run):
    try:
        from repro.obs import profile_totals
    except ImportError:  # a program from before the spans: no metric
        return None
    spans = profile_totals()["spans"]
    if "retire.epoch_commit" not in spans or not run.phases:
        return None
    return spans["retire.epoch_commit"][1] / run.phases * 1e3
