"""Durable phases per session admitted in the window: pfences tagged
``phase`` (one per combining phase) over sessions (exact count)."""


def read(run):
    if not run.sessions:
        return None
    return run.counters.get("pfence.phase", 0) / run.sessions
