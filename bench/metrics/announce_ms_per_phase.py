"""Milliseconds the clients spend in ``announce`` per phase (the
benchmark's spans around each client's call, summed over a phase)."""


def read(run):
    if not run.phases or "announce" not in run.spans:
        return None
    return sum(run.spans["announce"]) / run.phases * 1e3
