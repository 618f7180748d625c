"""Milliseconds in the tier's calls per session admitted in the window: the
benchmark's spans around submit, admit, mark_served and release."""


def read(run):
    if not run.sessions:
        return None
    names = ("submit", "admit", "mark_served", "release")
    return sum(sum(run.spans.get(n, ())) for n in names) / run.sessions * 1e3
