"""Seconds per crash in ``replay_pending`` (the benchmark's span)."""


def read(run):
    spans = run.spans.get("replay_pending")
    return sum(spans) / len(spans) if spans else None
