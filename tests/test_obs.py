"""Flight recorder: tracing is a pure observer of the durable path.

The ISSUE-7 acceptance criteria.  The obs package may never change what the
fabric persists: with tracing enabled, durable state must be BIT-IDENTICAL
and pwb/pfence counts (total and per tag) EXACTLY unchanged versus the
untraced run — on the serial pipelined path, the fused phase loop, and
through every crash point of the intent drain.  On top of that purity
gate, the recorder itself must be useful: the sidecar survives a crash as
a valid prefix with strictly monotone seq numbers, recovery EXTENDS it
with per-thread verdict events on the same timeline, and the metrics
registry yields sane percentiles and exporters.
"""

import json

import numpy as np
import pytest

import jax

from repro.checkpoint.dfc_checkpoint import CrashNow, FaultInjector, SimFS
from repro.core.jax_dfc import OP_ENQ
from repro.obs import (
    EV_EPOCH,
    EV_PFENCE,
    EV_PWB,
    EV_RECOVER,
    EV_SPAN,
    EV_VERDICT,
    FabricObserver,
    Histogram,
    MetricsRegistry,
    bridge_persist_stats,
    durable_digest,
    read_trace,
    to_chrome_trace,
)
from repro.runtime.dfc_shard import ShardedDFCRuntime, StaleTokenError

jax.config.update("jax_platform_name", "cpu")

CAP, LANES = 256, 16


def _schedule(n_rounds, n_threads, per_thread, seed=11):
    """Insert-only flat schedule with globally unique params (the
    exactly-once witness), round-major."""
    rng = np.random.default_rng(seed)
    val = 1.0
    sched = []
    for r in range(n_rounds):
        for t in range(n_threads):
            keys = [int(k) for k in rng.integers(0, 1000, per_thread)]
            params = [val + i for i in range(per_thread)]
            val += per_thread
            sched.append((t, r + 1, keys, [OP_ENQ] * per_thread, params))
    return sched


def _drive_fused(root, sched, *, n_threads, obs=None, injector=None):
    fs = SimFS(root, injector)
    rt = ShardedDFCRuntime(
        ["queue", "queue"], 2, CAP, LANES, fs=fs, n_threads=n_threads,
        obs=obs,
    )
    records = rt.phase_loop(sched)
    return fs, rt, records


def _drive_pipelined(root, sched, *, n_threads, obs=None):
    fs = SimFS(root)
    rt = ShardedDFCRuntime(
        ["queue", "queue"], 2, CAP, LANES, fs=fs, n_threads=n_threads,
        depth=2, obs=obs,
    )
    for (t, tok, keys, ops, params) in sched:
        rt.announce(t, keys, ops, params, token=tok)
        rt.combine_phase()
    rt.flush()
    return fs, rt


def _report_shape(report):
    """The comparable content of a recovery report (OpVerdicts flattened)."""
    shape = {}
    for t, r in report.items():
        shape[t] = {
            "token": r["token"],
            "applied": [bool(v.applied) for v in r["ops"]],
            "prev": None
            if not r.get("prev")
            else {
                "token": r["prev"]["token"],
                "applied": [bool(v.applied) for v in r["prev"]["ops"]],
            },
        }
    return shape


# ------------------------------------------------------------- purity gates
def test_traced_fused_run_is_bit_identical(tmp_path):
    """Fused phase loop: enabling the observer changes NOTHING durable —
    equal total stats, equal per-tag pstats, equal durable digest, equal
    records — while the trace itself is non-empty with monotone seqs."""
    sched = _schedule(3, 2, 4)
    fs1, _, recs1 = _drive_fused(tmp_path / "plain", sched, n_threads=2)
    obs = FabricObserver(root=tmp_path / "traced")
    fs2, _, recs2 = _drive_fused(
        tmp_path / "traced", sched, n_threads=2, obs=obs,
    )
    obs.flush()

    assert dict(fs1.stats) == dict(fs2.stats)
    assert fs1.pstats.as_dict() == fs2.pstats.as_dict()
    assert durable_digest(tmp_path / "plain") == durable_digest(
        tmp_path / "traced"
    )
    for a, b in zip(recs1, recs2):
        assert a["resp"] == b["resp"] and a["kinds"] == b["kinds"]

    events = read_trace(obs.trace_path)
    assert events, "observer recorded nothing"
    seqs = [e["seq"] for e in events]
    assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
    # the SimFS hooks mirror the real counters one-for-one
    assert sum(1 for e in events if e["ev"] == EV_PWB) == fs2.stats["pwb"]
    assert (
        sum(1 for e in events if e["ev"] == EV_PFENCE) == fs2.stats["pfence"]
    )


def test_traced_pipelined_run_is_bit_identical(tmp_path):
    """Same purity gate on the serial announce/combine/flush path."""
    sched = _schedule(3, 2, 4, seed=5)
    fs1, rt1 = _drive_pipelined(tmp_path / "plain", sched, n_threads=2)
    obs = FabricObserver(root=tmp_path / "traced")
    fs2, rt2 = _drive_pipelined(
        tmp_path / "traced", sched, n_threads=2, obs=obs,
    )
    obs.flush()
    assert dict(fs1.stats) == dict(fs2.stats)
    assert fs1.pstats.as_dict() == fs2.pstats.as_dict()
    assert durable_digest(tmp_path / "plain") == durable_digest(
        tmp_path / "traced"
    )
    for s in range(2):
        assert rt1.shard_contents(s) == rt2.shard_contents(s)
    # the observer's spans land in the recorder with their durations
    spans = [e for e in read_trace(obs.trace_path) if e["ev"] == EV_SPAN]
    assert {e["name"] for e in spans} >= {
        "fabric.announce", "combine", "retire", "retire.fetch", "retire.epoch_commit",
    }
    assert all(e["dur_us"] >= 0 for e in spans)


def test_read_responses_and_stale_token_unchanged_by_tracing(tmp_path):
    """Satellite (c): ``read_responses`` values and ``StaleTokenError``
    behavior are identical with the observer attached."""
    sched = _schedule(3, 2, 4, seed=3)
    vals = {}
    for name, obs in (
        ("plain", None),
        ("traced", FabricObserver(root=tmp_path / "traced")),
    ):
        _, rt, _ = _drive_fused(
            tmp_path / name, sched, n_threads=2, obs=obs,
        )
        for t in (0, 1):
            for tok in (2, 3):  # the two retained slots
                vals[(name, t, tok)] = rt.read_responses(t, token=tok)
            with pytest.raises(StaleTokenError):
                rt.read_responses(t, token=1)
    for t in (0, 1):
        for tok in (2, 3):
            a, b = vals[("plain", t, tok)], vals[("traced", t, tok)]
            assert a["resp"] == b["resp"] and a["kinds"] == b["kinds"]


# --------------------------------------------------------- crash + recovery
def test_crash_sweep_traced_matches_untraced(tmp_path):
    """Crash at EVERY persistence op of the fused drain with tracing on:
    the recovery report (per-thread verdicts) is identical to the untraced
    crash at the same op, the pre-crash sidecar is a valid JSONL prefix
    with monotone seqs, and recovery extends it with verdict events."""
    sched = _schedule(2, 2, 3, seed=42)
    # total op count from a dry (no-crash) run
    fs_dry, _, _ = _drive_fused(tmp_path / "dry", sched, n_threads=2)
    total = fs_dry.stats["pwb"] + fs_dry.stats["pfence"]
    assert total > 30

    for k in range(1, total + 1):
        reports = {}
        for name, traced in (("plain", False), ("traced", True)):
            root = tmp_path / f"k{k}_{name}"
            obs = FabricObserver(root=root) if traced else None
            inj = FaultInjector(crash_at=k)
            try:
                _drive_fused(
                    root, sched, n_threads=2, obs=obs, injector=inj,
                )
            except CrashNow:
                pass
            if traced:
                # the durable prefix: whatever flushed before the crash
                pre = read_trace(obs.trace_path)
                seqs = [e["seq"] for e in pre]
                assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
            fs2 = SimFS(root)
            obs2 = FabricObserver(root=root) if traced else None
            _, report = ShardedDFCRuntime.recover(
                fs2, kind=["queue", "queue"], n_shards=2, capacity=CAP,
                lanes=LANES, n_threads=2, obs=obs2,
            )
            reports[name] = _report_shape(report)
            if traced:
                post = read_trace(obs.trace_path)
                assert len(post) > len(pre), "recovery did not extend trace"
                seqs = [e["seq"] for e in post]
                assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
                stages = [
                    e["stage"] for e in post if e["ev"] == EV_RECOVER
                ]
                assert stages[-2:] == ["begin", "end"]
                n_verdicts = sum(1 for e in post if e["ev"] == EV_VERDICT)
                surfaced = sum(
                    1
                    for r in report.values()
                    if r["token"] is not None
                )
                assert n_verdicts == surfaced
        assert reports["plain"] == reports["traced"], f"verdicts diverge at op {k}"


def test_recovery_trace_continues_seq_numbering(tmp_path):
    """A fresh observer on an existing sidecar continues the seq timeline
    instead of restarting at 0 — crash forensics read as ONE ordered log."""
    sched = _schedule(2, 1, 3)
    obs = FabricObserver(root=tmp_path)
    _drive_fused(tmp_path, sched, n_threads=1, obs=obs)
    obs.flush()
    first = read_trace(obs.trace_path)
    obs2 = FabricObserver(root=tmp_path)
    fs2 = SimFS(tmp_path)
    ShardedDFCRuntime.recover(
        fs2, kind=["queue", "queue"], n_shards=2, capacity=CAP, lanes=LANES,
        n_threads=1, obs=obs2,
    )
    combined = read_trace(obs.trace_path)
    assert combined[: len(first)] == first  # strictly an extension
    assert combined[len(first)]["seq"] == first[-1]["seq"] + 1


def test_epoch_events_match_committed_epochs(tmp_path):
    """Every two-increment epoch commit lands one EV_EPOCH event whose
    final per-shard value equals the fabric's committed epoch."""
    sched = _schedule(3, 1, 4)
    obs = FabricObserver(root=tmp_path)
    _, rt, _ = _drive_fused(tmp_path, sched, n_threads=1, obs=obs)
    last = {}
    for e in obs.trace.events():
        if e["ev"] == EV_EPOCH:
            last[e["shard"]] = e["epoch"]
    for s, epoch in enumerate(rt.shard_epochs()):
        assert last.get(s, 0) == int(epoch)


# ------------------------------------------------------- metrics + exporters
def test_histogram_percentiles():
    h = Histogram()
    for v in range(1, 1001):  # 1..1000 ms
        h.record(float(v))
    s = h.summary()
    assert s["count"] == 1000
    assert s["min"] == 1.0 and s["max"] == 1000.0
    # log-bucketed: percentile lands within one quarter-octave of truth
    assert 400 <= s["p50"] <= 600
    assert 900 <= s["p99"] <= 1000
    assert abs(s["mean"] - 500.5) < 1e-6
    empty = Histogram()
    assert empty.percentile(0.5) == 0.0


def test_metrics_registry_snapshot_and_exporters(tmp_path):
    reg = MetricsRegistry()
    reg.counter("hits", shard=0)
    reg.counter("hits", 2, shard=0)
    reg.gauge("backlog", 7, shard=1)
    reg.observe("lat_ms", 4.0)
    snap = reg.snapshot()
    assert snap["counters"]["hits{shard=0}"] == 3
    assert snap["gauges"]["backlog{shard=1}"] == 7
    assert snap["histograms"]["lat_ms"]["count"] == 1
    n = reg.to_jsonl(tmp_path / "m.jsonl")
    lines = (tmp_path / "m.jsonl").read_text().splitlines()
    assert len(lines) == n and n == 3
    assert all(json.loads(line) for line in lines)


def test_chrome_trace_exporter(tmp_path):
    events = [
        {"seq": 0, "ts_us": 100, "ev": "announce", "thread": 1, "dur_us": 40},
        {"seq": 1, "ts_us": 200, "ev": "epoch_commit", "shard": 0},
    ]
    n = to_chrome_trace(events, tmp_path / "t.json")
    doc = json.loads((tmp_path / "t.json").read_text())
    assert n == 2 and len(doc) == 2  # bare-array Chrome trace format
    span, instant = doc
    assert span["ph"] == "X" and span["dur"] == 40 and span["ts"] == 60
    assert instant["ph"] == "i"
    to_chrome_trace([{"seq": 2, "ts_us": 50, "ev": EV_SPAN, "name": "retire.fetch",
                      "dur_us": 5}], tmp_path / "s.json")
    (program_span,) = json.loads((tmp_path / "s.json").read_text())
    assert program_span["name"] == "retire.fetch" and program_span["ts"] == 45


def test_bridge_persist_stats(tmp_path):
    fs = SimFS(tmp_path)
    fs.write("a", b"x", tag="announce")
    fs.fsync(["a"], tag="announce")
    fs.write("b", b"y")  # untagged -> default bucket
    reg = MetricsRegistry()
    bridge_persist_stats(reg, fs.pstats)
    c = reg.snapshot()["counters"]
    assert c["persist_pwb{tag=announce}"] == 1
    assert c["persist_pfence{tag=announce}"] == 1
    assert c["persist_pwb{tag=untagged}"] == 1
    assert c["persist_pwb_total"] == 2 and c["persist_pfence_total"] == 1


def test_persist_stats_snapshot_and_diff(tmp_path):
    fs = SimFS(tmp_path)
    fs.write("a", b"x", tag="slot")
    snap = fs.pstats.snapshot()
    fs.write("b", b"y", tag="slot")
    fs.fsync(["b"], tag="phase")
    d = fs.pstats.diff(snap)
    assert d.as_dict() == {"pwb": {"slot": 1}, "pfence": {"phase": 1}}
    assert snap.as_dict() == {"pwb": {"slot": 1}, "pfence": {}}  # immutable


# --------------------------------------------------------------- fabric_top
def test_fabric_top_renders_per_shard_table(tmp_path):
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "tools"))
    import fabric_top

    sched = _schedule(3, 1, 4)
    obs = FabricObserver(root=tmp_path)
    _drive_fused(tmp_path, sched, n_threads=1, obs=obs)
    obs.flush()
    events = read_trace(obs.trace_path)
    table = fabric_top.render(events)
    assert "shard" in table and "queue" in table
    assert "pwb" in table and "announce" in table
    agg = fabric_top.aggregate(events)
    assert sum(agg["pwb"].values()) == sum(
        1 for e in events if e["ev"] == EV_PWB
    )
    assert set(agg["commits"]) <= {0, 1}


# ------------------------------------------------------------- serving tier
def test_tier_latency_percentiles(tmp_path):
    """Satellite: the serving tier reports admission (and, once served,
    service/e2e) latency p50/p99 — and only when observed."""
    from repro.launch.serve import RequestQueueTier

    obs = FabricObserver()
    tier = RequestQueueTier(
        n_queues=2, slots=4, capacity=512, lanes=16, durable=True, obs=obs,
    )
    tier.submit([1, 2, 3, 4], [], None)
    admitted = tier.admit(4)
    assert admitted
    for sid, _slot in admitted:
        tier.mark_served(sid)
    stats = tier.latency_stats()
    assert stats is not None
    for name in ("admission_ms", "service_ms", "e2e_ms"):
        s = stats[name]
        assert s["count"] == len(admitted)
        assert 0 <= s["p50"] <= s["p99"]

    plain = RequestQueueTier(
        n_queues=2, slots=4, capacity=512, lanes=16, durable=True,
    )
    assert plain.latency_stats() is None
    plain.mark_served(1)  # no-op, not a crash


def test_tier_traced_run_is_bit_identical(tmp_path):
    """Purity holds through the serving tier too: identical durable stats
    and state with and without the observer."""
    from repro.launch.serve import RequestQueueTier

    waves = [([1, 2, 3], [], None), ([4, 5], [], None)]
    runs = {}
    for name, obs in (("plain", None), ("traced", FabricObserver())):
        fs = SimFS(tmp_path / name)
        tier = RequestQueueTier(
            n_queues=2, slots=2, capacity=512, lanes=16, durable=True,
            fs=fs, obs=obs,
        )
        rej = tier.submit_waves(waves)
        tier.admit(2)
        runs[name] = (rej, dict(fs.stats), fs.pstats.as_dict())
    assert runs["plain"] == runs["traced"]
    assert durable_digest(tmp_path / "plain") == durable_digest(
        tmp_path / "traced"
    )
