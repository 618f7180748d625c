"""Program spans and counters on the profiler's clock.

``span(name)`` and ``count(name, n)`` are the program's one span and counter
primitive.  A profiler session is the switch:

  * while no session records (``jax.profiler.TraceAnnotation.is_enabled()``
    is false), a site costs that one check: no allocation, no clock read;
  * while a session records, a span enters ``jax.profiler.TraceAnnotation``
    (so it lands in the trace on the same clock as the device's ops) and
    adds its inclusive duration and a count to a process-wide tally;
    ``count`` adds to the same tally.

The tally holds what ran during the most recent session only: the first
observation of a new session clears it.  Sessions are told apart by the
session object that ``jax.profiler.start_trace`` (and ``trace``) holds in
jax's private ``_profile_state``; a capture through a profiler server has
none, so across such captures the tally adds up.  ``profile_totals()`` reads it::

    {"spans": {name: (n, seconds)}, "counters": {name: value}}

jax is imported on first use, so ``repro.obs`` imports without it.
"""

from __future__ import annotations

import warnings
from time import perf_counter
from typing import Any, Dict, Optional, Tuple

_spans: Dict[str, list] = {}
_counters: Dict[str, float] = {}
_session: Any = None  # the session the tally belongs to
# bound on first use: jax.profiler.TraceAnnotation, and the state through
# which jax.profiler.start_trace holds its session object (a capture started
# through a profiler server has none, and reads as session None)
_annotation = None
_profile_state = None


def _is_enabled() -> bool:
    """Whether a profiler session records.  Rebinds itself to jax's own
    check on first call (to a constant False where jax is missing)."""
    global _is_enabled, _annotation, _profile_state
    try:
        from jax._src import profiler
        from jax.profiler import TraceAnnotation
    except ImportError:
        _is_enabled = lambda: False  # noqa: E731
    else:
        _annotation = TraceAnnotation
        _profile_state = getattr(profiler, "_profile_state", None)
        if _profile_state is None:
            warnings.warn(
                "jax.profiler has no _profile_state: the span tally cannot "
                "tell profiler sessions apart and adds up across them"
            )
        _is_enabled = TraceAnnotation.is_enabled
    return _is_enabled()


def recording() -> bool:
    """Whether spans and counters record now: guard the computation of a
    counter's value with it where that computation costs something."""
    return _is_enabled()


def _tally() -> Tuple[Dict[str, list], Dict[str, float]]:
    """The tally, cleared first when a new session has begun."""
    global _session
    sess = getattr(_profile_state, "profile_session", None)
    if sess is not _session:
        _spans.clear()
        _counters.clear()
        _session = sess
    return _spans, _counters


class _NullSpan:
    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class _Span:
    __slots__ = ("name", "ann", "t0")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        self.ann = _annotation(self.name)
        self.ann.__enter__()
        self.t0 = perf_counter()
        return None

    def __exit__(self, *exc):
        dt = perf_counter() - self.t0
        self.ann.__exit__(*exc)
        spans, _ = _tally()
        rec = spans.get(self.name)
        if rec is None:
            spans[self.name] = [1, dt]
        else:
            rec[0] += 1
            rec[1] += dt
        return False


def span(name: str, tag: Optional[str] = None):
    """Context manager timing ``name`` (``name.tag`` when ``tag`` is given;
    the string is built only while a session records)."""
    if not _is_enabled():
        return _NULL_SPAN
    return _Span(name if tag is None else f"{name}.{tag}")


def count(name: str, n: float = 1) -> None:
    """Add ``n`` to counter ``name`` while a session records."""
    if not _is_enabled():
        return
    _, counters = _tally()
    counters[name] = counters.get(name, 0) + n


def profile_totals() -> Dict[str, Dict[str, Any]]:
    """Spans ``{name: (n, seconds)}`` and counters ``{name: value}`` of the
    most recent profiler session (empty before the first)."""
    return {
        "spans": {k: (int(v[0]), float(v[1])) for k, v in _spans.items()},
        "counters": dict(_counters),
    }
