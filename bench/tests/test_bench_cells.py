"""Each cell's loop runs at a tiny size on the CPU against the reference: the
sound program comes out correct; the bfloat16 control and planted faults in
the timed path come out not correct."""

import numpy as np
import pytest

from bench_tiny import run_tiny

CELLS = ["queue.zipf5050", "queue.hot1", "queue.crash", "tier.burst"]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_is_correct(tmp_path, cell):
    result = run_tiny(tmp_path, cell)
    assert result["correct"], result["checks"]
    assert all(c["value"] == 0 for c in result["checks"].values())
    assert list(result)[-1] == "checks"
    assert "setup_s" in result["metrics"]
    assert result["device"]["platform"] == "cpu"


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(tmp_path, cell):
    result = run_tiny(tmp_path, cell, control=True)
    assert not result["correct"]
    assert result["checks"]["responses_wrong"]["value"] > 0


def _state_unchanged(monkeypatch):
    from repro.runtime import dfc_shard

    real = dfc_shard.hetero_multi_step

    def step(groups, table, keys, ops, params, meta, **kw):
        out = real(groups, table, keys, ops, params, meta, **kw)
        return (groups, meta) + tuple(out[2:])

    monkeypatch.setattr(dfc_shard, "hetero_multi_step", step)


def _half_batch(monkeypatch):
    from repro.runtime.dfc_shard import ShardedDFCRuntime

    real = ShardedDFCRuntime.announce

    def announce(self, thread, keys, ops, params, token):
        ops = np.asarray(ops).copy()
        ops[len(ops) // 2:] = 0  # the second half never reaches the combine
        return real(self, thread, keys, ops, params, token=token)

    monkeypatch.setattr(ShardedDFCRuntime, "announce", announce)


def _answer_altered(monkeypatch):
    from repro.runtime.dfc_shard import ShardedDFCRuntime

    real = ShardedDFCRuntime.read_responses

    def read(self, thread, token=None, lane=None):
        val = real(self, thread, token=token, lane=lane)
        if val is not None and val["resp"]:
            val = dict(val, resp=[val["resp"][0] + 1.0] + list(val["resp"][1:]))
        return val

    monkeypatch.setattr(ShardedDFCRuntime, "read_responses", read)


FAULTS = {"state_unchanged": _state_unchanged, "half_batch": _half_batch,
          "answer_altered": _answer_altered}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell", ["queue.zipf5050", "tier.burst"])
def test_fault_in_the_timed_path_is_not_correct(tmp_path, monkeypatch, cell, fault):
    FAULTS[fault](monkeypatch)
    try:
        result = run_tiny(tmp_path, cell)
    except Exception:  # a fault that breaks the run outright is caught too
        return
    assert not result["correct"], result["checks"]


def test_traced_run_reports_the_per_layer_metrics_it_can(tmp_path):
    result = run_tiny(tmp_path, "queue.hot1", trace=True)
    assert result["correct"]
    # counters and spans are read on any device; device-trace metrics need one
    for name in ("announce_ms_per_phase", "combine_phase_ms", "pfence_per_op",
                 "persist_bytes_per_op"):
        assert result["metrics"][name]["value"] > 0
    assert result["device"]["window_s"] > 0
    assert "breakdown" in result


def test_tier_with_a_queue_no_bucket_reaches_is_not_correct(tmp_path):
    """With 4 request queues the default table routes no bucket to queue 1:
    the deployment would hold a queue its traffic never fills."""
    import json

    from bench import harness
    from bench_tiny import spec, write_tiny

    data = write_tiny(tmp_path / "data")
    path = data / "configs" / "serving_tier.json"
    path.write_text(json.dumps(dict(json.loads(path.read_text()), queues=4)))
    result = harness.run_cell(spec(), "tier.burst", seed=11, seconds=1.0, trace=False,
                              data_dir=data, require_tpu=False,
                              cache_dir=tmp_path / "cache", log=lambda s: None)
    assert result["checks"]["queues_unfed"]["value"] == 1
    assert not result["correct"]
