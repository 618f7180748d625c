"""The harness around the cells: lookup by name, the roofline's byte count,
the trace reduction, the table of peaks and the refusal to run without a
TPU."""

import importlib.util
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench_tiny import BENCH, ROOT, write_tiny


def _reader(name):
    spec = importlib.util.spec_from_file_location(name, BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_combine_roofline_bytes_of_a_hand_worked_phase():
    roof = _reader("combine_roofline")
    # 6 live ops on 2 shards: 3 enqueues, 2 dequeues that return a value,
    # 1 dequeue that finds the queue empty, and 2 padding lanes
    ops = [1, 1, 2, 2, 1, 2, 0, 0]
    kinds = [1, 1, 2, 2, 1, 3, 0, 0]
    shards = [0, 0, 1, 1, 0, 1, 5, 5]
    # 6 x (12 + 8) in/out + 4 x (3 written + 2 read) slots + 16 x 2 roots
    assert roof.phase_bytes("queue", ops, kinds, shards) == 120 + 20 + 32


def test_combine_roofline_share_from_a_record():
    import types

    roof = _reader("combine_roofline")
    run = types.SimpleNamespace(
        trace={"programs": {"hetero_multi_step": 1e-3}}, kind="queue",
        device_kind="TPU v5 lite", phase_ops=[([1, 2], [1, 2], [0, 0])])
    # (2 x 20 + 2 x 4 + 16) B / 819e9 B/s / 1 ms
    assert roof.read(run) == pytest.approx(100 * 64 / 819e9 / 1e-3)
    run.trace = {"programs": {}}
    assert roof.read(run) is None


def test_peaks_table_names_its_source_and_refuses_unknown_devices(monkeypatch):
    from bench import harness

    table = json.loads((BENCH / "peaks.json").read_text())
    assert "Google Cloud" in table["source"]
    assert table["devices"]["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9
    with pytest.raises(harness.NoDevice):
        harness.find_device(1, require_tpu=True)  # the CPU is no TPU


def test_new_config_and_traffic_are_found_by_name(tmp_path):
    """A later cell adds a configuration file, a traffic file and an entry
    in BENCHMARK.json, and edits no existing file."""
    from bench import harness

    data = write_tiny(tmp_path / "data")
    cfg = json.loads((data / "configs" / "durable_queue.json").read_text())
    cfg.update(name="durable_stack", kind="stack", backlog=0)
    (data / "configs" / "durable_stack.json").write_text(json.dumps(cfg))
    mix = {"loop": "closed", "clients": 3, "ops_per_client": 10,
           "mix": {"push": 0.5, "pop": 0.5}, "keys": {"universe": 500, "skew": 0.5},
           "warmup_phases": 1}
    (data / "traffic" / "pairs.json").write_text(json.dumps(mix))
    spec = harness.load_spec()
    spec["workloads"].append({"name": "stack.pairs", "config": "durable_stack",
                              "traffic": "pairs", "chips": 1, "why": "test"})
    result = harness.run_cell(spec, "stack.pairs", seed=5, seconds=0.5, trace=False,
                              data_dir=data, require_tpu=False, cache_dir=tmp_path / "c",
                              log=lambda s: None)
    assert result["correct"], result["checks"]
    assert set(result["metrics"]) == {"setup_s"}


def test_trace_reduction_of_hand_made_events():
    from bench import trace_reduce as tr

    events = {
        "host": [("window", 0, 100), ("combine_phase", 10, 60), ("announce", 70, 90)],
        "devices": {"/device:TPU:0": {
            "XLA Ops": [("fusion.1", 20, 30), ("copy.2", 25, 40), ("fusion.1", 80, 85),
                        ("late", 120, 130)],
            "XLA Modules": [("jit_hetero_multi_step(7)", 20, 40), ("jit_other(1)", 80, 85)],
        }},
    }
    out = tr.reduce_events(events, ["combine_phase", "announce"])
    assert out["window_s"] == pytest.approx(100e-9)
    assert out["busy_s"] == pytest.approx(25e-9)  # [20, 40] and [80, 85]
    assert out["programs"] == {"hetero_multi_step": pytest.approx(20e-9),
                               "other": pytest.approx(5e-9)}
    assert dict(out["device_ops"])["fusion.1"] == pytest.approx(15e-9)
    idle = dict(out["idle_gaps"])
    # gaps [0,20] [40,80] [85,100]: combine_phase covers 10..20 and 40..60,
    # announce 70..80 and 85..90, the rest is host:other
    assert idle["combine_phase"] == pytest.approx(30e-9)
    assert idle["announce"] == pytest.approx(15e-9)
    assert idle["host:other"] == pytest.approx(30e-9)


def _run_py(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "queue.hot1", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_run_without_a_tpu_fails_and_prints_no_result():
    proc = _run_py(ROOT, {})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_run_with_only_the_benchmark_files_fails(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench", ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    proc = _run_py(tmp_path, {})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_trace_reduction_of_a_trace_recorded_on_the_chip():
    """Events of a 1 s queue.hot1 window traced on one TPU v5 lite: the
    device lines and the benchmark's host spans, as ``read_events`` read
    them from the profiler's ``.xplane.pb``."""
    import gzip

    from bench import trace_reduce as tr

    doc = json.loads(gzip.decompress((BENCH / "tests" / "data" / "hot1_trace_events.json.gz").read_bytes()))
    out = tr.reduce_events(doc["events"], ["announce", "combine_phase", "read_responses"])
    assert out["window_s"] == pytest.approx(1.033044279)
    assert out["busy_s"] == pytest.approx(0.008350081)
    assert out["programs"]["hetero_multi_step"] == pytest.approx(0.007579158)
    assert out["device_ops"][0] == ["fusion f32[16384] fusion.6", pytest.approx(0.003904079)]
    idle = dict(out["idle_gaps"])
    assert idle["combine_phase"] == pytest.approx(0.525911612)
    assert idle["announce"] == pytest.approx(0.410165568)
    assert idle["read_responses"] == pytest.approx(0.073740634)
    assert out["busy_s"] + sum(idle.values()) == pytest.approx(out["window_s"])


def test_read_events_finds_the_benchmark_spans(tmp_path):
    import jax
    import jax.numpy as jnp

    from bench import trace_reduce as tr

    f = jax.jit(lambda x: x * 2)
    f(jnp.ones(8)).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("window"):
        with jax.profiler.TraceAnnotation("announce"):
            f(jnp.ones(8)).block_until_ready()
    jax.profiler.stop_trace()
    events = tr.read_events(tr.find_trace(tmp_path))
    names = [n for n, _, _ in events["host"]]
    assert "window" in names and "announce" in names
    assert tr.reduce_events(events, ["announce"])["window_s"] > 0
