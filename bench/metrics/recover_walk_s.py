"""Seconds per crash in ``ShardedDFCRuntime.recover`` (the benchmark's span)."""


def read(run):
    spans = run.spans.get("recover")
    return sum(spans) / len(spans) if spans else None
