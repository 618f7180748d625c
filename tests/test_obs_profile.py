"""Program spans and counters on the profiler's clock (``repro.obs.span``,
``count``, ``profile_totals``).

A profiler session is the switch: without one a site costs one
``is_enabled()`` check and the tally stays empty; under one, every span
lands in the trace nested inside its parent, the tally holds that session's
totals only, and the durable path is unchanged.
"""

import numpy as np
import pytest

import jax
from jax.profiler import ProfileData

from repro.checkpoint.dfc_checkpoint import SimFS
from repro.core.jax_dfc import OP_DEQ, OP_ENQ
from repro.obs import count, durable_digest, profile_totals, span
from repro.obs import profile as obs_profile
from repro.runtime.dfc_shard import ShardedDFCRuntime

CAP, LANES, SHARDS, THREADS = 256, 16, 2, 2

# each span of one depth-1 phase -> the span it runs inside
PARENT = {
    "announce.ring": "fabric.announce",
    "pfence.announce": "fabric.announce",
    "combine.collect": "combine",
    "combine.dispatch": "combine",
    "retire": "combine",
    "retire.fetch": "retire",
    "retire.serialize": "retire",
    "retire.responses": "retire",
    "pfence.phase": "retire",
    "retire.epoch_commit": "retire",
    "pfence.epoch": "retire.epoch_commit",
}
ROOTS = ("fabric.announce", "combine", "fabric.read_responses")


def _fabric(root, depth=1):
    return ShardedDFCRuntime(
        "queue", SHARDS, CAP, LANES, fs=SimFS(root), n_threads=THREADS,
        depth=depth,
    )


def _phase(rt, token, rng, read=True):
    """Every thread announces 4 ops on random keys, one combine, and (with
    ``read``) every thread reads its durable response."""
    for t in range(THREADS):
        ops = np.array([OP_ENQ, OP_ENQ, OP_DEQ, OP_ENQ])
        params = np.arange(4, dtype=np.float32) + 100 * token + 10 * t
        rt.announce(t, rng.integers(0, 1000, 4), ops, params, token=token)
    rt.combine_phase()
    for t in range(THREADS if read else 0):
        assert rt.read_responses(t, token=token) is not None


def _host_events(log_dir):
    """(name, start_ns, end_ns, line) of every host event of the trace."""
    (path,) = sorted(log_dir.rglob("*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:CPU"):
            for i, line in enumerate(plane.lines):
                out += [(e.name, e.start_ns, e.start_ns + e.duration_ns, i)
                        for e in line.events]
    return out


def test_no_session_leaves_the_tally_empty(tmp_path, monkeypatch):
    """Without a profiler session a site is one check: the null span is one
    shared object, no clock is read, and nothing reaches the tally."""
    monkeypatch.setattr(obs_profile, "_spans", {})
    monkeypatch.setattr(obs_profile, "_counters", {})
    rt = _fabric(tmp_path / "fab")
    _phase(rt, 1, np.random.default_rng(0))  # compile outside the check

    def no_clock():
        raise AssertionError("a span read the clock with no session recording")

    monkeypatch.setattr(obs_profile, "perf_counter", no_clock)
    assert span("a") is span("b", "c")
    with span("a"):
        count("d2h_bytes", 5)
    _phase(rt, 2, np.random.default_rng(1))
    assert profile_totals() == {"spans": {}, "counters": {}}


def test_session_records_every_span_nested_and_the_bytes_fetched(tmp_path, monkeypatch):
    rng = np.random.default_rng(3)
    rt = _fabric(tmp_path / "fab")
    _phase(rt, 1, rng)  # compile, and make every thread's valid record durable

    fetched = []
    real = ShardedDFCRuntime._retire

    def spy(self, fl):
        arrays = [fl[k] for k in ("resp", "kinds", "epochs", "phases_cum",
                                  "ops_cum", "epochs_before")]
        arrays += jax.tree_util.tree_leaves(fl["states"])
        fetched.append(sum(np.asarray(a).nbytes for a in arrays))
        return real(self, fl)

    monkeypatch.setattr(ShardedDFCRuntime, "_retire", spy)
    jax.profiler.start_trace(str(tmp_path / "t1"))
    try:
        _phase(rt, 2, rng)
    finally:
        jax.profiler.stop_trace()
    totals = profile_totals()
    spans = totals["spans"]
    assert set(spans) >= set(PARENT) | set(ROOTS), sorted(spans)
    assert spans["combine"][0] == spans["retire"][0] == 1
    assert spans["fabric.announce"][0] == spans["fabric.read_responses"][0] == THREADS
    assert spans["pfence.announce"][0] == 2 * THREADS
    assert spans["pfence.phase"][0] == 1
    for child, parent in PARENT.items():
        assert spans[child][1] <= spans[parent][1]
    assert totals["counters"]["d2h_bytes"] == sum(fetched) > 0
    touched = spans["retire.serialize"][0]
    counters = totals["counters"]
    assert counters["slot_leaves_written"] + counters["slot_leaves_elided"] + counters[
        "slot_leaves_inlined"] == touched * len(jax.tree_util.tree_leaves(rt._shard_state(0)))

    # in the trace, every child lies inside an event of its parent's name on
    # the same host thread
    events = _host_events(tmp_path / "t1")
    names = {n for n, *_ in events}
    assert names >= set(PARENT) | set(ROOTS)
    for name, a, b, line in events:
        if name in PARENT:
            assert any(n == PARENT[name] and pa <= a and b <= pb and pl == line
                       for n, pa, pb, pl in events), name

    # a second session's totals hold that session alone
    jax.profiler.start_trace(str(tmp_path / "t2"))
    try:
        with span("combine"):
            count("d2h_bytes", 7)
    finally:
        jax.profiler.stop_trace()
    assert profile_totals()["counters"] == {"d2h_bytes": 7}
    assert set(profile_totals()["spans"]) == {"combine"}
    assert profile_totals()["spans"]["combine"][0] == 1


def test_depth_guard_span_holds_the_forced_retire(tmp_path):
    """At depth 3 a thread's third announcement reuses the slot of a chain
    still in flight: ``announce.retire_wait`` holds that chain's retire."""
    rng = np.random.default_rng(5)
    rt = _fabric(tmp_path / "fab", depth=3)
    jax.profiler.start_trace(str(tmp_path / "t"))
    try:
        for tok in (1, 2):
            rt.announce(0, rng.integers(0, 1000, 4), np.full(4, OP_ENQ),
                        np.arange(4.0) + 10 * tok, token=tok)
            rt.combine_phase()
        rt.announce(0, rng.integers(0, 1000, 4), np.full(4, OP_ENQ),
                    np.arange(4.0) + 30, token=3)
    finally:
        jax.profiler.stop_trace()
    spans = profile_totals()["spans"]
    assert spans["announce.retire_wait"][0] == 1
    assert spans["retire"][0] == 1
    events = _host_events(tmp_path / "t")
    (wait,) = [(a, b) for n, a, b, _ in events if n == "announce.retire_wait"]
    (ret,) = [(a, b) for n, a, b, _ in events if n == "retire"]
    assert wait[0] <= ret[0] and ret[1] <= wait[1]


@pytest.mark.parametrize("depth", [1, 2])
def test_purity_gate_holds_under_a_profiler_session(tmp_path, depth):
    """Spans and counters recording changes nothing durable: per-tag
    pwb/pfence counts and the durable digest equal the unprofiled run's."""
    fabrics = {}
    for name in ("plain", "profiled"):
        rt = _fabric(tmp_path / name, depth=depth)
        if name == "profiled":
            jax.profiler.start_trace(str(tmp_path / "trace"))
        try:
            rng = np.random.default_rng(9)
            for tok in range(1, 4):
                _phase(rt, tok, rng, read=depth == 1)
            rt.flush()
        finally:
            if name == "profiled":
                jax.profiler.stop_trace()
        fabrics[name] = rt
    plain, profiled = fabrics["plain"], fabrics["profiled"]
    assert profile_totals()["spans"]["retire"][0] >= 3
    assert dict(plain.fs.stats) == dict(profiled.fs.stats)
    assert plain.fs.pstats.as_dict() == profiled.fs.pstats.as_dict()
    assert durable_digest(tmp_path / "plain") == durable_digest(tmp_path / "profiled")
    for s in range(SHARDS):
        assert plain.shard_contents(s) == profiled.shard_contents(s)


def test_sessions_are_told_apart_by_jax_profile_state(tmp_path):
    """The tally tells sessions apart by the session object that
    ``jax.profiler.start_trace`` keeps in jax's private ``_profile_state``.
    A jax that moves it fails here, not silently in a tally that adds up
    across sessions."""
    from jax._src import profiler as jax_profiler

    state = jax_profiler._profile_state
    assert state.profile_session is None
    jax.profiler.start_trace(str(tmp_path / "t"))
    try:
        with span("x"):
            pass
        assert obs_profile._profile_state is state
        assert state.profile_session is not None
        assert obs_profile._session is state.profile_session
    finally:
        jax.profiler.stop_trace()
    assert state.profile_session is None


def test_store_reads_are_the_fenced_announcement_records(tmp_path, monkeypatch):
    """A steady phase reads each thread's announcement record from the store
    three times (the ready scan, the response merge of ``_retire``, and
    ``read_responses``); ``disk_reads`` and ``disk_read_bytes`` count just
    those reads."""
    rng = np.random.default_rng(11)
    rt = _fabric(tmp_path / "fab")
    _phase(rt, 1, rng)
    reads = []
    real = SimFS.read_durable

    def spy(self, rel):
        data = real(self, rel)
        if data is not None:
            reads.append((rel, len(data)))
        return data

    monkeypatch.setattr(SimFS, "read_durable", spy)
    jax.profiler.start_trace(str(tmp_path / "t"))
    try:
        _phase(rt, 2, rng)
    finally:
        jax.profiler.stop_trace()
    counters = profile_totals()["counters"]
    assert counters["disk_reads"] == len(reads) == 3 * THREADS
    assert counters["disk_read_bytes"] == sum(n for _, n in reads)
    for t in range(THREADS):
        assert sum(rel.startswith(f"tAnn/thread_{t}/ann") for rel, _ in reads) == 3
