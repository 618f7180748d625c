"""The trace reduction charges idle gaps to host spans in one sweep: it gives
what the gap-by-span loop it replaced gave, to the last bit, on a trace
recorded on the chip and on random traces, and reduces a trace the size of a
30 s queue.hot1 window in seconds."""

import collections
import gzip
import json
import random
import time

import pytest

from bench_tiny import BENCH

HOT1_SPANS = ["announce", "combine_phase", "read_responses"]


def reduce_events_by_loop(events, span_names, top=10):
    """The reduction as it was before the sweep, kept as the oracle: every
    gap looks at every span, shortest first."""
    from bench.trace_reduce import (MODULES_LINE, OPS_LINE, WINDOW_SPAN, clip, gaps,
                                    merge, op_name, program_name, total)

    windows = [(a, b) for n, a, b in events["host"] if n == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one '{WINDOW_SPAN}' host span, found {len(windows)}")
    lo, hi = windows[0]
    window_ns = hi - lo
    spans = sorted(((n, a, b) for n, a, b in events["host"] if n in set(span_names)),
                   key=lambda s: s[2] - s[1])
    busy_per_device, programs, op_time = [], collections.Counter(), collections.Counter()
    idle = collections.Counter()
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


def _same(got, want):
    """Equal to the last bit, key order and list order included."""
    assert json.dumps(got) == json.dumps(want)


def test_sweep_equals_the_loop_on_a_trace_recorded_on_the_chip():
    from bench import trace_reduce as tr

    doc = json.loads(gzip.decompress(
        (BENCH / "tests" / "data" / "hot1_trace_events.json.gz").read_bytes()))
    for names in (HOT1_SPANS, ["announce"], ["read_responses", "combine_phase"], []):
        _same(tr.reduce_events(doc["events"], names), reduce_events_by_loop(doc["events"], names))


def _random_trace(seed, n_ops=400, n_spans=300, n_devices=2):
    """Ops on each device and host spans over [0, 10^6): spans nest (a call
    inside a call), overlap without nesting, abut end to start, repeat a
    name, sit wholly in busy time, have no length, or reach past the window;
    every other start and end is an integer, as the profiler's are."""
    rng = random.Random(seed)
    lo, hi = 1000, 999_000

    def t():
        x = rng.uniform(0, 1_000_000)
        return float(int(x)) if rng.random() < 0.5 else x

    host = [("window", float(lo), float(hi))]
    names = ["announce", "combine_phase", "read_responses", "submit", "other"]
    while len(host) < n_spans:
        a = t()
        b = a + rng.choice([0.0, rng.uniform(1, 50), rng.uniform(50, 5000), rng.uniform(5e3, 2e5)])
        host.append((rng.choice(names), a, b))
        shape = rng.random()
        if shape < 0.3:  # nested inside
            ia = a + (b - a) * rng.random() * 0.5
            host.append((rng.choice(names), ia, ia + (b - ia) * rng.random()))
        elif shape < 0.5:  # abutting
            host.append((rng.choice(names), b, b + rng.uniform(1, 3000)))
        elif shape < 0.6:  # overlapping, not nested
            host.append((rng.choice(names), (a + b) / 2, b + rng.uniform(1, 3000)))
        elif shape < 0.65:  # the same span twice
            host.append((host[-1][0], a, b))
    rng.shuffle(host)
    devices = {}
    for d in range(n_devices):
        ops, mods = [], []
        for _ in range(n_ops):
            a = t()
            b = a + rng.choice([rng.uniform(1, 20), rng.uniform(20, 4000)])
            ops.append((f"fusion.{rng.randrange(6)}", a, b))
            if rng.random() < 0.3:
                mods.append((f"jit_step_{rng.randrange(3)}({d})", a, b))
        devices[f"/device:TPU:{d}"] = {"XLA Ops": ops, "XLA Modules": mods}
    devices["/device:CUSTOM:Megascale Trace"] = {}
    return {"host": host, "devices": devices}


@pytest.mark.parametrize("seed", range(8))
def test_sweep_equals_the_loop_on_random_traces(seed):
    from bench import trace_reduce as tr

    events = _random_trace(seed)
    names = ["announce", "combine_phase", "read_responses", "submit"]
    _same(tr.reduce_events(events, names), reduce_events_by_loop(events, names))
    _same(tr.reduce_events(events, names[:1], top=3), reduce_events_by_loop(events, names[:1], top=3))


def test_a_30s_hot1_window_reduces_in_seconds():
    """A 30 s hot1 window: ~300 phases a second, each an announce, a combine
    and a read per client; idle gaps between short device ops."""
    from bench import trace_reduce as tr

    phases, clients, phase_ns = 4000, 4, 7_500_000
    host = [("window", 0.0, float(phases * phase_ns))]
    ops = []
    for p in range(phases):
        t0 = p * phase_ns
        for c in range(clients):
            host.append(("announce", t0 + c * 1e6, t0 + c * 1e6 + 9e5))
            host.append(("read_responses", t0 + 6e6 + c * 3e5, t0 + 6e6 + c * 3e5 + 2e5))
        host.append(("combine_phase", t0 + 4.1e6, t0 + 5.9e6))
        for k in range(14):  # 14 short ops a phase: 13 gaps between them
            a = t0 + 4.2e6 + k * 1e5
            ops.append(("fusion.1", a, a + 5e4))
    events = {"host": host, "devices": {"/device:TPU:0": {"XLA Ops": ops, "XLA Modules": []}}}
    assert len(host) - 1 >= 30_000 and len(ops) >= 50_000
    t = time.perf_counter()
    out = tr.reduce_events(events, HOT1_SPANS)
    assert time.perf_counter() - t < 10.0
    idle = dict(out["idle_gaps"])
    assert out["busy_s"] + sum(idle.values()) == pytest.approx(out["window_s"])
    # the combine's 13 gaps of 50 us a phase, and the edges of its span
    assert idle["combine_phase"] == pytest.approx(phases * (1.8e6 - 14 * 5e4) * 1e-9)
