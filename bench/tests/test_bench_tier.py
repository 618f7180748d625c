"""The serving tier's driver at a tiny size on the CPU: it reports the
benchmark's end-to-end metrics, and drives ``RequestQueueTier`` as
``ContinuousServer.run`` does: whole submits of up to ``max_submit``
sessions, ``admit`` with every free decode slot, and nothing compiled inside
the window."""

import re
import time

import pytest

from bench_tiny import spec, write_tiny


def tier_spec():
    """The benchmark with the pending cells, and tier.burst reporting the
    end-to-end metrics of the queue cells."""
    doc = spec()
    for m in doc["end_to_end"]:
        if m["name"] in ("ops_per_s", "p95_ms"):
            m["workloads"] = m["workloads"] + ["tier.burst"]
    return doc


def run_tier(tmp_path, log=lambda s: None, seed=2**31 + 11, seconds=1.0):
    from bench import harness

    return harness.run_cell(tier_spec(), "tier.burst", seed=seed, seconds=seconds,
                            trace=False, data_dir=write_tiny(tmp_path / "data"),
                            require_tpu=False, cache_dir=tmp_path / "cache", log=log)


@pytest.fixture
def driver(tmp_path):
    """A set-up tier driver at the tiny size, outside any window."""
    from bench import harness, tier

    config, traffic = harness.load_parts("serving_tier", "burst", write_tiny(tmp_path / "data"))
    ctx = harness.Context(seed=5, traced=False, store=tmp_path / "store",
                          cache_dir=tmp_path / "cache", control=False)
    drv = tier.Driver(config, traffic, ctx, lambda s: None)
    drv.setup()
    return drv


def _record(monkeypatch, obj, name, calls):
    real = getattr(obj, name)

    def wrapped(*args, **kw):
        calls.append((args, kw))
        return real(*args, **kw)

    monkeypatch.setattr(obj, name, wrapped)


def test_tier_reports_ops_per_s_and_p95_ms_and_is_correct(tmp_path):
    result = run_tier(tmp_path)
    assert result["correct"], result["checks"]
    assert set(result["metrics"]) == {"ops_per_s", "p95_ms", "setup_s"}
    assert result["metrics"]["ops_per_s"]["value"] > 0
    assert result["metrics"]["p95_ms"]["value"] > 0
    assert result["attempted"] > 0 and result["failed"] == 0


def test_three_due_sessions_go_in_one_submit(driver, monkeypatch):
    calls = []
    _record(monkeypatch, driver.tier, "submit", calls)
    now = time.perf_counter()
    sids = [driver.next_sid + i for i in range(3)]
    for sid in sids:
        driver.due[sid] = now
    driver.waiting = list(sids)
    driver._iteration()
    arrivals = [args[0] for args, _ in calls if args and args[0]]
    assert arrivals == [sids]  # one phase of 3, not 2 + 1
    assert sorted(driver.admitted[-3:]) == sids


def test_submits_are_cut_at_max_submit_only(driver, monkeypatch):
    calls = []
    _record(monkeypatch, driver.tier, "submit", calls)
    n = 2 * driver.max_submit + 3
    sids = list(range(driver.next_sid, driver.next_sid + n))
    now = time.perf_counter()
    for sid in sids:
        driver.due[sid] = now
    driver.waiting = list(sids)
    driver._iteration()
    arrivals = [list(args[0]) for args, _ in calls if args and args[0]]
    assert [len(a) for a in arrivals] == [driver.max_submit, driver.max_submit, 3]
    assert sum(arrivals, []) == sids  # due order


def test_admit_is_called_with_the_free_slot_count(driver, monkeypatch):
    calls = []
    _record(monkeypatch, driver.tier, "admit", calls)
    now = time.perf_counter()
    for rnd in range(3):
        sid = driver.next_sid + rnd
        driver.due[sid] = now
        driver.waiting = [sid]
        driver._iteration()
    assert [args for args, _ in calls] == [(driver.slots,)] * 3
    assert driver.slots == 8  # every slot free, not a power of two of the queued


def test_warm_up_covers_the_largest_phase(driver):
    assert driver.max_phase() == max(2 * driver.max_submit, driver.slots)
    driver.max_submit = driver.lanes + 1
    with pytest.raises(ValueError, match="lanes"):
        driver._warm_shapes()


def test_no_backend_compile_in_the_window(tmp_path):
    lines = []
    result = run_tier(tmp_path, log=lines.append, seed=2**31 + 29, seconds=2.0)
    assert result["correct"], result["checks"]
    window = [l for l in lines if l.startswith("window:")]
    assert len(window) == 1
    assert re.search(r"backend compiles in window: 0;", window[0]), window[0]
