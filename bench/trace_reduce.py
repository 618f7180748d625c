"""Reduce a profiler trace (``.xplane.pb``) of one benchmark window to numbers.

What it gives, over the window the benchmark marked with its ``window``
host span:

* ``busy_s``: the union of the intervals in which an operation ran on a
  device, averaged over the devices that ran any;
* ``window_s``: the length of the window;
* ``programs``: device seconds per compiled program, by its jit name
  (``jit_hetero_multi_step(...)`` counts under ``hetero_multi_step``);
* ``device_ops``: the operations that took most device time, by short name;
* ``idle_gaps``: device idle time inside the window, by the benchmark host
  span that was open while the device idled (``host:other`` where none was).

Device events are read from the ``XLA Ops`` and ``XLA Modules`` lines of each
``/device:`` plane; the benchmark's spans are ``jax.profiler.TraceAnnotation``
events on the host plane.  The profiler puts both on one clock.

Run it on a saved trace to look at one by hand::

    python3 bench/trace_reduce.py path/to/host.xplane.pb
"""

from __future__ import annotations

import collections
import json
import re
import sys
from pathlib import Path
from typing import Dict, Iterable, List, Sequence, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "window"

Interval = Tuple[float, float]


def find_trace(log_dir) -> Path:
    """The one ``.xplane.pb`` file a profiler session wrote under ``log_dir``."""
    found = sorted(Path(log_dir).rglob("*.xplane.pb"))
    if len(found) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, found {found}")
    return found[0]


def program_name(event_name: str) -> str:
    """``jit_hetero_multi_step(123)`` -> ``hetero_multi_step``."""
    name = event_name.split("(", 1)[0]
    return re.sub(r"^jit_", "", name)


def op_name(event_name: str) -> str:
    """A device op's short name: ``%fusion.5 = f32[16384]{...} fusion(...)``
    -> ``fusion f32[16384] fusion.5``."""
    parts = event_name.split(" ")
    if len(parts) > 3 and parts[1] == "=":
        return f"{parts[3].split('(')[0]} {parts[2].split('{')[0]} {parts[0].lstrip('%')}"
    return event_name[:80]


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


def total(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """Idle intervals of [lo, hi] between merged busy intervals."""
    out, cur = [], lo
    for a, b in busy:
        if a > cur:
            out.append((cur, a))
        cur = max(cur, b)
    if hi > cur:
        out.append((cur, hi))
    return out


def read_events(path) -> Dict[str, object]:
    """The parts of a trace the reduction uses, as plain lists of
    (name, start_ns, end_ns): device ops and modules per device plane, and
    every host event of the benchmark's spans (any name)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    devices: Dict[str, Dict[str, list]] = {}
    host: List[Tuple[str, float, float]] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            lines = {}
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    lines[line.name] = [
                        (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events
                    ]
            devices[plane.name] = lines
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                host += [
                    (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events
                ]
    return {"devices": devices, "host": host}


def reduce_events(events, span_names: Sequence[str], top: int = 10) -> Dict[str, object]:
    """Reduce :func:`read_events` output; ``span_names`` are the benchmark's
    host spans that idle gaps are attributed to (innermost first)."""
    windows = [(a, b) for n, a, b in events["host"] if n == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one '{WINDOW_SPAN}' host span, found {len(windows)}")
    lo, hi = windows[0]
    window_ns = hi - lo
    # shortest first: a gap goes to the innermost call the host was in
    spans = sorted(((n, a, b) for n, a, b in events["host"] if n in set(span_names)),
                   key=lambda s: s[2] - s[1])
    busy_per_device, programs, op_time = [], collections.Counter(), collections.Counter()
    idle: collections.Counter = collections.Counter()
    for lines in events["devices"].values():
        ops = clip([(a, b) for _, a, b in lines.get(OPS_LINE, [])], lo, hi)
        if not ops:
            continue
        busy = merge(ops)
        busy_per_device.append(total(busy))
        for name, a, b in lines.get(OPS_LINE, []):
            for ca, cb in clip([(a, b)], lo, hi):
                op_time[op_name(name)] += cb - ca
        for name, a, b in lines.get(MODULES_LINE, []):
            for ca, cb in clip([(a, b)], lo, hi):
                programs[program_name(name)] += cb - ca
        for ga, gb in gaps(busy, lo, hi):
            left = gb - ga
            for name, a, b in spans:
                if left <= 0:
                    break
                cover = total(clip([(a, b)], ga, gb))
                if cover > 0:
                    cover = min(cover, left)
                    idle[name] += cover
                    left -= cover
            if left > 0:
                idle["host:other"] += left
    if not busy_per_device:
        return {"busy_s": 0.0, "window_s": window_ns * 1e-9, "programs": {},
                "device_ops": [], "idle_gaps": []}
    n_dev = len(busy_per_device)
    return {
        "busy_s": sum(busy_per_device) / n_dev * 1e-9,
        "window_s": window_ns * 1e-9,
        "programs": {k: v / n_dev * 1e-9 for k, v in programs.items()},
        "device_ops": [[k, v / n_dev * 1e-9] for k, v in op_time.most_common(top)],
        "idle_gaps": [[k, v / n_dev * 1e-9] for k, v in idle.most_common(top)],
    }


def reduce_trace(path, span_names: Sequence[str]) -> Dict[str, object]:
    return reduce_events(read_events(path), span_names)


def _describe(path) -> None:
    """Print the planes and lines of a trace, with sample events."""
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(str(path)).planes:
        print("plane", plane.name)
        for line in plane.lines:
            evs = list(line.events)
            print("  line", repr(line.name), len(evs),
                  [(e.name, e.start_ns, e.duration_ns) for e in evs[:3]])


if __name__ == "__main__":
    _describe(sys.argv[1])
    if len(sys.argv) > 2:
        print(json.dumps(reduce_trace(sys.argv[1], sys.argv[2].split(",")), indent=1))
