"""The per-layer metrics read from the program's own spans and counters: a
traced run reports each of them, and the spans land in the profiler's trace
inside the benchmark's ``window`` span, on the profiler's one clock."""

import sys
import types

import numpy as np
import pytest

from bench_tiny import run_tiny

PROGRAM_METRICS = ("fetch_ms_per_phase", "serialize_ms_per_phase", "phase_pfence_ms",
                   "epoch_commit_ms_per_phase", "dispatch_ms_per_phase",
                   "announce_pfence_ms_per_phase", "d2h_bytes_per_op")


@pytest.mark.parametrize("cell", ["queue.zipf5050", "queue.hot1"])
def test_traced_run_reports_the_program_span_metrics(tmp_path, cell):
    from bench.fabric import Driver
    from repro.obs import profile_totals

    result = run_tiny(tmp_path, cell, trace=True)
    assert result["correct"], result["checks"]
    for name in PROGRAM_METRICS:
        assert result["metrics"][name]["value"] > 0, name
    # the trace reduction attributes idle time by span name: no program span
    # may share a name with one of the benchmark's own
    assert not set(profile_totals()["spans"]) & set(Driver.span_names)
    # the fetch holds at least the stacked values leaf, every phase
    cfg_values = 4 * 1024 * 4  # tiny config: 4 shards x 1,024 float32 slots
    ops_per_phase = {"queue.zipf5050": 4 * 16, "queue.hot1": 4 * 8}[cell]
    assert result["metrics"]["d2h_bytes_per_op"]["value"] >= cfg_values / ops_per_phase


def test_read_events_finds_the_fetch_inside_the_window(tmp_path):
    import jax

    from bench import trace_reduce as tr
    from repro.checkpoint.dfc_checkpoint import SimFS
    from repro.core.jax_dfc import OP_DEQ, OP_ENQ
    from repro.runtime.dfc_shard import ShardedDFCRuntime

    rt = ShardedDFCRuntime("queue", 2, 256, 16, fs=SimFS(tmp_path / "fab"), n_threads=1)

    def phase(tok):
        rt.announce(0, np.arange(4) + tok, np.array([OP_ENQ, OP_ENQ, OP_DEQ, OP_ENQ]),
                    np.arange(4.0) + 10 * tok, token=tok)
        rt.combine_phase()

    phase(1)  # compile outside the trace
    jax.profiler.start_trace(str(tmp_path / "trace"))
    try:
        with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
            phase(2)
            phase(3)
    finally:
        jax.profiler.stop_trace()
    events = tr.read_events(tr.find_trace(tmp_path / "trace"))
    (window,) = [(a, b) for n, a, b in events["host"] if n == tr.WINDOW_SPAN]
    fetches = [(a, b) for n, a, b in events["host"] if n == "retire.fetch"]
    assert len(fetches) == 2
    assert all(window[0] <= a <= b <= window[1] for a, b in fetches)


@pytest.mark.parametrize("name", PROGRAM_METRICS)
def test_reader_gives_no_value_without_the_program_spans(monkeypatch, name):
    """The benchmark's files also run over a program from before the spans:
    there, and where the tally is empty, a reader gives no value and raises
    nothing."""
    from bench.harness import metric_reader
    from repro.obs import profile as obs_profile

    run = types.SimpleNamespace(phases=10, acked_ops=100)
    read = metric_reader(name)
    monkeypatch.setattr(obs_profile, "_spans", {})
    monkeypatch.setattr(obs_profile, "_counters", {})
    assert read(run) is None
    monkeypatch.setitem(sys.modules, "repro.obs", types.ModuleType("repro.obs"))
    assert read(run) is None
