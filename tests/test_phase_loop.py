"""Fused K-phase device dispatch (``phase_loop``): parity + crash harness.

Covers the ISSUE-6 tentpole acceptance criteria: a whole SCHEDULE of
combining phases runs as ONE device dispatch (``lax.scan`` over the phase
axis, on the vmapped combine or the Pallas shard-grid kernels),
accumulating per-phase persist INTENTS in device arrays; the host then
drains the intent log and issues
the pwb/pfence batches behind the device.  The durable schedule the drain
replays is op-for-op the serial one, so:

- responses, shard contents, and fs.stats (pwb AND pfence counts) must
  equal a serial ``announce``/``combine_phase``/``flush`` drive of the same
  schedule, and the ``sequential_hetero_reference`` oracle;
- a crash at EVERY persistence op of the intent drain — announcement
  mirror writes, shard pwbs, response publishes, epoch increments — must
  recover with per-thread detectability verdicts intact and replay to
  exactly-once (the device is up to K phases ahead of the host at every
  one of these points: the dispatch completed before the drain started);
- the vmapped-jnp and Pallas-grid backends must be bit-identical;
- the donated phase-loop program (the one the chip runs) must behave
  exactly like the undonated one.

Fast representatives run in tier-1; the full kind x backend sweep grid
is ``slow``.
"""

import numpy as np
import pytest

import jax

from repro.checkpoint.dfc_checkpoint import CrashNow, FaultInjector, SimFS
from repro.core.jax_dfc import OP_ENQ, OP_PUSH, OP_PUSHR
from repro.runtime.dfc_shard import (
    ShardedDFCRuntime,
    StaleTokenError,
    sequential_hetero_reference,
)

jax.config.update("jax_platform_name", "cpu")

CAP, LANES = 256, 16
PUSH_OF = {"stack": OP_PUSH, "queue": OP_ENQ, "deque": OP_PUSHR}


def _schedule(kinds, n_rounds, n_threads, per_thread, seed=11, mixed=False):
    """Flat [(thread, token, keys, ops, params)] schedule, one phase per
    entry, round-major (every thread announces token r+1 in round r).
    Insert-only with globally unique params unless ``mixed``."""
    rng = np.random.default_rng(seed)
    val = 1.0
    sched = []
    for r in range(n_rounds):
        for t in range(n_threads):
            keys = [int(k) for k in rng.integers(0, 1000, per_thread)]
            if mixed:
                ops = [int(o) for o in rng.integers(1, 3, per_thread)]
            else:
                ops = [PUSH_OF[kinds[0]]] * per_thread
            params = [val + i for i in range(per_thread)]
            val += per_thread
            sched.append((t, r + 1, keys, ops, params))
    return sched


def _drive_serial(rt, sched):
    """The reference drive: round-lockstep announce/combine/flush, reading
    every response — the durable schedule phase_loop must reproduce."""
    out = []
    by_tok = {}
    for entry in sched:
        by_tok.setdefault(entry[1], []).append(entry)
    for tok in sorted(by_tok):
        for (t, tk, k, o, p) in by_tok[tok]:
            rt.announce(t, k, o, p, token=tk)
        rt.combine_phase()
        rt.flush()
        for (t, tk, _k, _o, _p) in by_tok[tok]:
            out.append(rt.read_responses(t, token=tk))
    return out


def _fabric_contents(rt):
    return sorted(sum((rt.shard_contents(s) for s in range(rt.n_shards)), []))


# -------------------------------------------------------------- parity
def test_phase_loop_matches_serial_drive(tmp_path):
    """The fused loop's responses, final contents, and EXACT pwb/pfence
    counts equal the serial drive's — the drain replays the serial durable
    schedule behind the single device dispatch."""
    kinds = ["queue", "stack", "deque"]
    sched = _schedule(kinds, 4, 2, 5, mixed=True)
    # chain=2 keeps the two threads' announcements as separate batches in
    # the serial dispatch — the per-(thread, token) phase granularity the
    # fused schedule uses
    fs1 = SimFS(tmp_path / "serial")
    rt1 = ShardedDFCRuntime(
        kinds, 3, CAP, LANES, fs=fs1, n_threads=2, chain=2,
    )
    serial = _drive_serial(rt1, sched)

    fs2 = SimFS(tmp_path / "fused")
    rt2 = ShardedDFCRuntime(kinds, 3, CAP, LANES, fs=fs2, n_threads=2)
    records = rt2.phase_loop(sched)

    assert dict(fs1.stats) == dict(fs2.stats), "pwb/pfence parity broken"
    assert len(records) == len(sched)
    for rec, want in zip(records, serial):
        assert rec["resp"] == want["resp"]
        assert rec["kinds"] == want["kinds"]
        assert rec["targets"] == want["targets"]
    for s in range(3):
        assert rt1.shard_contents(s) == rt2.shard_contents(s)


def test_phase_loop_matches_oracle(tmp_path):
    """Phase-for-phase parity with ``sequential_hetero_reference`` on a
    mixed insert/remove schedule."""
    kinds = ["queue", "stack", "deque"]
    sched = _schedule(kinds, 3, 2, 6, seed=5, mixed=True)
    fs = SimFS(tmp_path)
    rt = ShardedDFCRuntime(kinds, 3, CAP, LANES, fs=fs, n_threads=2)
    records = rt.phase_loop(sched)
    lists = [[] for _ in range(3)]
    for rec, (t, tok, keys, ops, params) in zip(records, sched):
        resp, kk = sequential_hetero_reference(
            kinds, lists, list(keys), list(ops), list(params), LANES,
            table=rt.table,
        )
        assert np.allclose(rec["resp"], resp)
        assert rec["kinds"] == kk


def test_phase_loop_records_match_read_responses(tmp_path):
    """The returned records ARE the durable responses: the last two tokens
    per thread stay readable through ``read_responses`` and match; older
    tokens raise ``StaleTokenError``."""
    kinds = ["queue", "queue"]
    sched = _schedule(kinds, 3, 2, 4)
    fs = SimFS(tmp_path)
    rt = ShardedDFCRuntime(kinds, 2, CAP, LANES, fs=fs, n_threads=2)
    records = rt.phase_loop(sched)
    by_thread_tok = {(r["thread"], r["token"]): r for r in records}
    for t in (0, 1):
        for tok in (2, 3):  # the two retained slots
            val = rt.read_responses(t, token=tok)
            rec = by_thread_tok[(t, tok)]
            assert val["resp"] == rec["resp"]
            assert val["kinds"] == rec["kinds"]
        with pytest.raises(StaleTokenError):
            rt.read_responses(t, token=1)


def test_phase_loop_scan_grid_parity(tmp_path):
    """The phase scan over the vmapped jnp combine and over the Pallas
    shard-grid kernels produce identical records, durable stats, and
    contents."""
    kinds = ["queue", "stack", "deque"]
    sched = _schedule(kinds, 3, 2, 5, seed=3, mixed=True)
    runs = {}
    for backend in ("jnp", "pallas"):
        fs = SimFS(tmp_path / backend)
        rt = ShardedDFCRuntime(
            kinds, 3, CAP, LANES, fs=fs, n_threads=2, backend=backend,
        )
        recs = rt.phase_loop(sched)
        runs[backend] = (recs, dict(fs.stats), _fabric_contents(rt))
    recs_s, stats_s, cont_s = runs["jnp"]
    recs_g, stats_g, cont_g = runs["pallas"]
    assert stats_s == stats_g
    assert cont_s == cont_g
    for a, b in zip(recs_s, recs_g):
        assert a["resp"] == b["resp"] and a["kinds"] == b["kinds"]
        assert a["targets"] == b["targets"]


@pytest.fixture
def donated(monkeypatch):
    """Run ``phase_loop`` through the donated program, as it runs off the
    CPU: the old kind-group buffers are consumed by the dispatch, so any
    later read of them raises instead of passing silently."""
    from repro.runtime import dfc_shard

    monkeypatch.setattr(
        dfc_shard, "_phase_loop_step_plain", dfc_shard._phase_loop_step_donated
    )


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_phase_loop_donated_matches_serial_drive(tmp_path, donated, backend):
    """The donated phase loop consumes the old group buffers and still
    reproduces the serial drive's records, counts, and contents."""
    kinds = ["queue", "stack", "deque", "map"]
    sched = _schedule(kinds, 3, 2, 5, seed=13, mixed=True)
    fs1 = SimFS(tmp_path / "serial")
    rt1 = ShardedDFCRuntime(
        kinds, 4, CAP, LANES, fs=fs1, n_threads=2, chain=2, backend=backend,
    )
    serial = _drive_serial(rt1, sched)

    fs2 = SimFS(tmp_path / "fused")
    rt2 = ShardedDFCRuntime(
        kinds, 4, CAP, LANES, fs=fs2, n_threads=2, backend=backend,
    )
    before = jax.tree_util.tree_leaves(rt2.groups)
    records = rt2.phase_loop(sched[:4])
    assert all(leaf.is_deleted() for leaf in before), "groups not donated"
    records += rt2.phase_loop(sched[4:])  # a second dispatch on the outputs

    assert dict(fs1.stats) == dict(fs2.stats)
    for rec, want in zip(records, serial):
        assert rec["resp"] == want["resp"]
        assert rec["kinds"] == want["kinds"]
        assert rec["targets"] == want["targets"]
    for s in range(4):
        assert rt1.shard_contents(s) == rt2.shard_contents(s)


def test_phase_loop_donated_crash_sweep(tmp_path, donated):
    """Crash/recover/replay around the donated program: every third
    persistence op of the drain, exactly-once after each."""
    _sweep(tmp_path, ["queue", "stack"], seed=29, step=3)


def test_phase_loop_empty_and_single_phase(tmp_path):
    """Degenerate schedules: empty -> no durable traffic, single phase ->
    one combining phase, same as the serial path."""
    fs = SimFS(tmp_path)
    rt = ShardedDFCRuntime(["queue"], 1, CAP, LANES, fs=fs, n_threads=1)
    assert rt.phase_loop([]) == []
    assert fs.stats["pwb"] == 0 and fs.stats["pfence"] == 0
    recs = rt.phase_loop([(0, 1, [1, 2], [OP_ENQ] * 2, [1.0, 2.0])])
    assert len(recs) == 1
    assert recs[0]["resp"] == [0.0, 0.0]  # R_ACK carries no value payload
    assert recs[0]["kinds"] == [1, 1]
    assert _fabric_contents(rt) == [1.0, 2.0]


# -------------------------------------------------------- crash sweeps
def _crash_scenario(tmp, crash_at, kinds, sched, *, n_threads,
                    backend="ref"):
    inj = FaultInjector(crash_at=crash_at)
    fs = SimFS(tmp, inj)
    n_shards = len(kinds)
    rt = ShardedDFCRuntime(
        kinds, n_shards, CAP, LANES, fs=fs, n_threads=n_threads,
        backend=backend,
    )
    try:
        rt.phase_loop(sched)
    except CrashNow:
        pass
    rt2, report = ShardedDFCRuntime.recover(
        fs.crash(), kind=kinds, n_shards=n_shards, capacity=CAP,
        lanes=LANES, n_threads=n_threads, backend=backend,
    )
    return rt2, report, inj.count


def _verify_exactly_once(rt2, report, sched, *, n_threads):
    """Soundness: every op a verdict reports applied is durably in the
    fabric.  Completeness: replay the announced-not-applied ops, re-drive
    the never-announced phases through a fresh fused loop, and check every
    submitted value lands exactly once."""
    assert all(int(e) % 2 == 0 for e in rt2.shard_epochs())
    history = {(t, tok): params for (t, tok, _k, _o, params) in sched}
    contents = _fabric_contents(rt2)
    assert len(contents) == len(set(contents)), "duplicate after recovery"
    for t in range(n_threads):
        r = report[t]
        for rec in ([r] if r["token"] is not None else []) + (
            [r["prev"]] if r.get("prev") else []
        ):
            params = history[(t, rec["token"])]
            for i, v in enumerate(rec["ops"]):
                if v.applied:
                    assert params[i] in contents, (t, rec["token"], i)
    rt2.replay_pending(report)
    surfaced = {t: report[t]["token"] or 0 for t in range(n_threads)}
    remaining = [e for e in sched if e[1] > surfaced[e[0]]]
    if remaining:
        rt2.phase_loop(remaining)
    expect = sorted(p for (_t, _tok, _k, _o, ps) in sched for p in ps)
    assert _fabric_contents(rt2) == expect, "lost or duplicated ops"


def _sweep(tmp_path, kinds, *, n_threads=2, n_rounds=3, per_thread=4,
           step=1, seed=42, backend="ref"):
    sched = _schedule(kinds, n_rounds, n_threads, per_thread, seed=seed)
    _rt_dry, report_dry, total = _crash_scenario(
        tmp_path / "dry", None, kinds, sched, n_threads=n_threads,
        backend=backend,
    )
    assert total > 40  # the drain really is issuing the serial op count
    for k in range(1, total + 1, step):
        rt2, report, _ = _crash_scenario(
            tmp_path / f"k{k}", k, kinds, sched, n_threads=n_threads,
            backend=backend,
        )
        _verify_exactly_once(rt2, report, sched, n_threads=n_threads)


def test_phase_loop_crash_sweep_queue(tmp_path):
    """Acceptance representative: crash at EVERY persistence op of the
    intent drain on a 2-shard queue fabric — at each point the device has
    already finished ALL K phases and the host is mid-drain."""
    _sweep(tmp_path, ["queue", "queue"])


def test_phase_loop_crash_sweep_mixed(tmp_path):
    """Heterogeneous representative: queue+stack fabric, crash at every
    persistence op."""
    _sweep(tmp_path, ["queue", "stack"], seed=7)


def test_crash_device_ahead_of_host(tmp_path):
    """Directed ISSUE-6 case: crash BETWEEN the device finishing the whole
    K-phase dispatch and the host persisting the FIRST phase's intents
    (persistence op 1 of the drain).  Recovery must find no phase applied
    — the device's K phases of intents are all lost with the volatile
    arrays — and a full re-drive lands every value exactly once."""
    kinds = ["queue", "queue"]
    sched = _schedule(kinds, 2, 2, 3, seed=9)
    rt2, report, _ = _crash_scenario(
        tmp_path, 1, kinds, sched, n_threads=2,
    )
    for t in (0, 1):
        assert report[t]["token"] is None  # nothing announced durably
    assert _fabric_contents(rt2) == []
    _verify_exactly_once(rt2, report, sched, n_threads=2)


def test_crash_between_phases_k_and_k_minus_1(tmp_path):
    """Directed: crash with phase k-1 fully committed and phase k's intents
    still undrained — the recovered fabric is exactly the phase-(k-1)
    prefix, and the rest replays exactly once.  The crash point lands on
    the first announce pwb of phase 2's drain (phase 1 = 3 announce pwbs +
    2 pfences, 2 shard-leaf pwbs + meta, response pwb + pfence, 3 epoch
    ops)."""
    kinds = ["queue", "queue"]
    sched = _schedule(kinds, 3, 1, 2, seed=21)
    # dry run to count phase 1's ops, then crash right after them
    fs_dry = SimFS(tmp_path / "dry")
    rt_dry = ShardedDFCRuntime(kinds, 2, CAP, LANES, fs=fs_dry, n_threads=1)
    rt_dry.phase_loop(sched[:1])
    ops_phase1 = fs_dry.stats["pwb"] + fs_dry.stats["pfence"]
    rt2, report, _ = _crash_scenario(
        tmp_path, ops_phase1 + 1, kinds, sched, n_threads=1,
    )
    # phase 1 committed, phase 2 announced at the crash op but not durable
    assert _fabric_contents(rt2) == sorted(sched[0][4])
    _verify_exactly_once(rt2, report, sched, n_threads=1)


@pytest.mark.slow
@pytest.mark.parametrize("kind", ["stack", "queue", "deque"])
@pytest.mark.parametrize("backend", ["ref", "jnp", "pallas"])
def test_phase_loop_crash_sweep_grid(tmp_path, kind, backend):
    """Full grid: crash at every persistence op for each structure kind on
    every backend (the Pallas shard grid in interpret mode)."""
    _sweep(tmp_path, [kind, kind], seed=17, backend=backend)


def test_request_tier_bulk_waves_match_serial_submits():
    """The serving tier rides the fused loop: ``submit_waves`` commits K
    arrival rounds in one dispatch with the same rejections, durable
    stats, and final queue contents as K ``submit`` calls."""
    from repro.launch.serve import RequestQueueTier

    waves = [
        ([1, 2, 3], [], None),
        ([4, 5], [], None),
        ([6, 7, 8, 9], [], None),
    ]
    t1 = RequestQueueTier(
        n_queues=2, slots=2, capacity=512, lanes=16, durable=True,
    )
    rej_serial = [t1.submit(s, r, p) for (s, r, p) in waves]
    t2 = RequestQueueTier(
        n_queues=2, slots=2, capacity=512, lanes=16, durable=True,
    )
    rej_waves = t2.submit_waves(waves)
    assert rej_waves == rej_serial
    assert dict(t1.rt.fs.stats) == dict(t2.rt.fs.stats)
    for s in range(t1.rt.n_shards):
        assert t1.rt.shard_contents(s) == t2.rt.shard_contents(s)
