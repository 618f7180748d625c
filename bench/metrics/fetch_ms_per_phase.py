"""Milliseconds a phase spends fetching the fused step's outputs to the host:
the program span ``retire.fetch`` (device wait and device-to-host copy),
summed over the profiler's window, per phase."""


def read(run):
    try:
        from repro.obs import profile_totals
    except ImportError:  # a program from before the spans: no metric
        return None
    spans = profile_totals()["spans"]
    if "retire.fetch" not in spans or not run.phases:
        return None
    return spans["retire.fetch"][1] / run.phases * 1e3
