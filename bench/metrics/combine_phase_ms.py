"""Milliseconds in ``combine_phase`` per phase: at depth 1 it holds the fused
device step's dispatch, the device-to-host fetch, the persist, the phase's
pfence and the per-shard epoch commits (the benchmark's span)."""


def read(run):
    spans = run.spans.get("combine_phase")
    if not spans:
        return None
    return sum(spans) / len(spans) * 1e3
