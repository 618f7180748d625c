#!/usr/bin/env python3
"""Smoke run of the durable flat-combining fabric on one TPU chip.

Drives the fabric's main path once, through its normal entry points, at a
deployment's sizes, and holds every answer to the plain sequential
reference (``sequential_hetero_reference``):

  A  durable work queue, the paper's evaluation deployment: 64 queue shards
     x 65,536 slots (16 MiB of values on the device), 256 lanes, a backlog
     of 1,048,576 items committed through announced phases, then 1,024-op
     batches of 50% enqueue / 50% dequeue over Zipf-0.99 keys: serial
     announce -> combine_phase -> flush phases, one fused ``phase_loop`` of
     8 phases, and a second one crashed mid-drain, recovered, replayed and
     re-driven — responses, verdicts and final contents exactly the
     reference's.
  B  the same fabric path on the Pallas kernels: a mixed fabric of 16
     stack, 16 queue, 16 deque and 16 map shards of 65,536 slots (a map
     shard holds 8,192 buckets of 8), run on backend ``jnp`` and on
     ``pallas`` over one schedule — bit-identical to each other and to the
     reference, and the Pallas run's compiled programs hold Mosaic kernels
     (``tpu_custom_call``), not the interpreter.
  C  the serving tier: the durable request-queue launcher (tier only, no
     model) admits and serves 4,096 sessions; each is served exactly once.

Run it from the repository root on a machine with one TPU chip::

    python chip_smoke.py

It exits non-zero, and prints no result line, unless JAX's first device is
a TPU and every phase agrees with its reference.  The last line of standard
output is then ``{"ok": true, "device": {...}}``; every earlier line is
smoke-run bookkeeping (sizes, device bytes, compile and wall seconds) and
not a benchmark measurement.
"""

from __future__ import annotations

import collections
import json
import sys
import tempfile
import time
import traceback
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent

SIZES = dict(
    a_shards=64,
    capacity=65_536,
    lanes=256,
    batch=1024,
    prefill_phases=64,  # x 64 shards x 256 lanes = 1,048,576 items
    prefill_k=16,  # pre-fill phases per fused dispatch
    serial_phases=3,
    loop_k=8,
    b_shards_per_kind=16,
    sessions=4096,
    session_batch=64,
    key_universe=1_000_000,
    zipf=0.99,
    seed=0,
)


def log(phase: str, **fields) -> None:
    print(f"smoke-run {phase}: {json.dumps(fields, sort_keys=True)}", flush=True)


class CompileLog:
    """Backend compile seconds per program and persistent-cache traffic,
    from JAX's own monitoring events."""

    def __init__(self):
        import jax

        self.seconds = collections.defaultdict(float)
        self.events = collections.Counter()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds[kw.get("fun_name", "?")] += duration

    def _event(self, event, **kw):
        if event.startswith("/jax/compilation_cache/cache_"):
            self.events[event.rsplit("/", 1)[1]] += 1

    def take(self):
        """Compile seconds per program since the last call, largest first."""
        out = dict(sorted(self.seconds.items(), key=lambda kv: -kv[1]))
        self.seconds.clear()
        return {k: round(v, 3) for k, v in out.items()}


def _agree(got_resp, got_kinds, want_resp, want_kinds, what):
    got_r = np.asarray(got_resp, np.float32)
    want_r = np.asarray(want_resp, np.float32)
    if list(got_kinds) != list(want_kinds) or not np.array_equal(got_r, want_r):
        bad = [
            i for i in range(len(want_kinds))
            if got_kinds[i] != want_kinds[i] or got_r[i] != want_r[i]
        ]
        raise AssertionError(
            f"{what}: {len(bad)} ops disagree with the reference, first at "
            f"lane {bad[0]}: got ({got_r[bad[0]]}, {got_kinds[bad[0]]}) "
            f"want ({want_r[bad[0]]}, {want_kinds[bad[0]]})"
        )
    return len(want_kinds)


def _contents_agree(rt, oracle, what):
    for s in range(rt.n_shards):
        got, want = rt.shard_contents(s), oracle[s]
        if rt.kinds[s] == "map":
            got = sorted(got)
            want = sorted((int(k), float(v)) for k, v in want.items())
        else:
            want = [float(v) for v in want]
        if got != want:
            raise AssertionError(
                f"{what}: shard {s} ({rt.kinds[s]}) holds {len(got)} items "
                f"that differ from the reference's {len(want)}"
            )
    return sum(len(o) for o in oracle)


class Traffic:
    """Seeded batches: Zipf keys, unique integer-valued params (exact in
    f32), ops drawn per target shard kind or as a fixed enq/deq mix."""

    def __init__(self, sizes, first_value):
        self.rng = np.random.default_rng(sizes["seed"])
        self.sizes = sizes
        self.next_value = first_value

    def _zipf(self, n):
        from repro.runtime.dfc_shard import zipf_keys

        return zipf_keys(
            self.rng, n, self.sizes["key_universe"], self.sizes["zipf"]
        )

    def values(self, n):
        out = np.arange(self.next_value, self.next_value + n, dtype=np.float64)
        self.next_value += n
        return out

    def enq_deq(self, n):
        from repro.core.jax_dfc import OP_DEQ, OP_ENQ

        ops = self.rng.permutation(np.repeat([OP_ENQ, OP_DEQ], n // 2))
        params = np.where(ops == OP_ENQ, self.values(n), 0.0)
        return self._zipf(n), ops, params

    def mixed(self, kinds, n):
        """Op codes drawn per key, valid for the kind of its target shard
        (identity routing table)."""
        from repro.core.jax_dfc import STRUCTS
        from repro.runtime.dfc_shard import route_keys_host

        keys = self._zipf(n)
        opmax = np.asarray([STRUCTS[k].n_opcodes for k in kinds])
        ops = self.rng.integers(1, opmax[route_keys_host(keys, len(kinds))])
        return keys, ops, self.values(n)


def _reference(kinds, oracle, entry, lanes, capacity):
    from repro.runtime.dfc_shard import R_OVERFLOW, sequential_hetero_reference

    _t, _tok, keys, ops, params = entry
    resp, kinds_out = sequential_hetero_reference(
        kinds, oracle, [int(k) for k in keys], [int(o) for o in ops],
        [float(p) for p in params], lanes, capacity=capacity,
    )
    # a lane overflow would be replayed after a crash, which the reference
    # does not model; the seeded traffic never overflows
    if R_OVERFLOW in kinds_out:
        raise AssertionError("traffic overflowed a shard's lanes")
    return resp, kinds_out


# ------------------------------------------------------------------ phase A
def phase_a(sizes, tmp: Path):
    from repro.checkpoint.dfc_checkpoint import CrashNow, SimFS
    from repro.core.jax_dfc import OP_ENQ
    from repro.runtime.dfc_shard import ShardedDFCRuntime

    n_sh, cap, lanes = sizes["a_shards"], sizes["capacity"], sizes["lanes"]
    kinds = ["queue"] * n_sh
    fs = SimFS(tmp / "queue")
    rt = ShardedDFCRuntime("queue", n_sh, cap, lanes, fs=fs, n_threads=1)
    oracle = [[] for _ in range(n_sh)]
    checked = 0
    token = 0

    def ref(entry):
        return _reference(kinds, oracle, entry, lanes, None)

    # pre-fill through announced phases: every phase enqueues ``lanes``
    # items on every shard (one key per shard), committed durably
    fill_keys = np.repeat([rt.key_for_shard(s) for s in range(n_sh)], lanes)
    fill_ops = np.full(fill_keys.shape, OP_ENQ)
    t0 = time.perf_counter()
    sched = []
    for _ in range(sizes["prefill_phases"]):
        token += 1
        first = 1 + (token - 1) * fill_keys.size
        params = np.arange(first, first + fill_keys.size, dtype=np.float64)
        sched.append((0, token, fill_keys, fill_ops, params))
    records = []
    for i in range(0, len(sched), sizes["prefill_k"]):
        records += rt.phase_loop(sched[i : i + sizes["prefill_k"]])
    prefill_s = time.perf_counter() - t0
    for rec, entry in zip(records, sched):
        resp, kk = ref(entry)
        checked += _agree(rec["resp"], rec["kinds"], resp, kk, "A pre-fill")
    backlog = int(rt.shard_sizes().sum())
    if backlog != len(sched) * fill_keys.size:
        raise AssertionError(f"A pre-fill left a backlog of {backlog}")
    log(
        "A", step="prefill", path="announced phases via phase_loop",
        items=backlog, phases=len(sched), seconds=round(prefill_s, 3),
    )

    traffic = Traffic(sizes, first_value=1 + backlog)
    batch = sizes["batch"]

    # serial durable phases: announce -> combine_phase -> flush
    t0 = time.perf_counter()
    for _ in range(sizes["serial_phases"]):
        token += 1
        keys, ops, params = traffic.enq_deq(batch)
        rt.announce(0, keys, ops, params, token=token)
        rt.combine_phase()
        rt.flush()
        val = rt.read_responses(0, token=token)
        resp, kk = ref((0, token, keys, ops, params))
        checked += _agree(val["resp"], val["kinds"], resp, kk, "A serial")
    log("A", step="serial", phases=sizes["serial_phases"],
        seconds=round(time.perf_counter() - t0, 3))

    # one fused phase loop
    def loop_schedule():
        nonlocal token
        out = []
        for _ in range(sizes["loop_k"]):
            token += 1
            out.append((0, token) + traffic.enq_deq(batch))
        return out

    sched = loop_schedule()
    t0 = time.perf_counter()
    before = fs.injector.count
    records = rt.phase_loop(sched)
    loop_ops = fs.injector.count - before
    for rec, entry in zip(records, sched):
        resp, kk = ref(entry)
        checked += _agree(rec["resp"], rec["kinds"], resp, kk, "A phase_loop")
    log("A", step="phase_loop", phases=len(sched), persist_ops=loop_ops,
        seconds=round(time.perf_counter() - t0, 3))

    # crash mid-drain of a second fused loop, recover, replay, re-drive
    sched = loop_schedule()
    wants = [ref(entry) for entry in sched]
    fs.injector.crash_at = fs.injector.count + loop_ops // 2
    t0 = time.perf_counter()
    try:
        rt.phase_loop(sched)
        raise AssertionError("the injected crash did not fire")
    except CrashNow:
        crashed_at = fs.injector.count
    del rt
    rt, report = ShardedDFCRuntime.recover(
        fs.crash(), kind="queue", n_shards=n_sh, capacity=cap, lanes=lanes,
        n_threads=1,
    )
    recover_s = time.perf_counter() - t0
    rep = report[0]
    tokens = [e[1] for e in sched]
    j = tokens.index(rep["token"]) if rep["token"] in tokens else -1
    applied = replayed = 0
    if j >= 0:
        want_r, want_k = wants[j]
        redo = []
        for i, v in enumerate(rep["ops"]):
            if v.applied:
                applied += 1
                _agree([v.resp], [v.kind], [want_r[i]], [want_k[i]],
                       "A applied verdict")
            else:
                redo.append(i)
        rt.replay_pending(report)
        if redo:
            val = rt.read_responses(0, token=rep["token"])
            replayed = _agree(
                val["resp"], val["kinds"], [want_r[i] for i in redo],
                [want_k[i] for i in redo], "A replay",
            )
        checked += applied + replayed
    redrive = sched[j + 1:]
    for rec, (resp, kk) in zip(rt.phase_loop(redrive), wants[j + 1:]):
        checked += _agree(rec["resp"], rec["kinds"], resp, kk, "A re-drive")
    items = _contents_agree(rt, oracle, "A final contents")
    log(
        "A", step="crash", crash_op=crashed_at, crashed_phase=j,
        verdicts_applied=applied, replayed=replayed,
        redriven_phases=len(redrive), recover_seconds=round(recover_s, 3),
        seconds=round(time.perf_counter() - t0, 3),
    )
    return {"ops_checked": checked, "final_items": items}


# ------------------------------------------------------------------ phase B
def _mixed_kinds(sizes):
    per = sizes["b_shards_per_kind"]
    return sum(([k] * per for k in ("stack", "queue", "deque", "map")), [])


def _run_fabric(backend, sizes, tmp, batches):
    from repro.checkpoint.dfc_checkpoint import SimFS
    from repro.runtime.dfc_shard import ShardedDFCRuntime

    kinds = _mixed_kinds(sizes)
    fs = SimFS(tmp / f"mixed_{backend}")
    rt = ShardedDFCRuntime(
        kinds, len(kinds), sizes["capacity"], sizes["lanes"], fs=fs,
        n_threads=1, backend=backend,
    )
    out = []
    n_serial = sizes["serial_phases"]
    for entry in batches[:n_serial]:
        _t, token, keys, ops, params = entry
        rt.announce(0, keys, ops, params, token=token)
        rt.combine_phase()
        rt.flush()
        val = rt.read_responses(0, token=token)
        out.append((val["resp"], val["kinds"]))
    out += [(r["resp"], r["kinds"]) for r in rt.phase_loop(batches[n_serial:])]
    return rt, out


def _kernel_calls(rt, sizes):
    """``tpu_custom_call`` count in the compiled combine-phase and fused
    phase-loop programs of ``rt``'s fabric (0 for an interpreted kernel)."""
    import jax.numpy as jnp

    from repro.runtime import dfc_shard

    counts = {}
    for name, fn, k in (
        ("combine_phase", dfc_shard.hetero_multi_step, 1),
        ("phase_loop", dfc_shard._phase_loop_step_donated, sizes["loop_k"]),
    ):
        shape = (k, sizes["batch"])
        text = fn.lower(
            rt.groups, jnp.asarray(rt.table), jnp.zeros(shape, jnp.int32),
            jnp.zeros(shape, jnp.int32), jnp.zeros(shape, jnp.float32),
            rt.meta, kinds=tuple(rt.kinds), lanes=rt.lanes,
            backend=rt.backend, unroll=rt.depth,
        ).compile().as_text()
        counts[name] = text.count("tpu_custom_call")
    return counts


def phase_b(sizes, tmp: Path):
    kinds = _mixed_kinds(sizes)
    traffic = Traffic(dict(sizes, seed=sizes["seed"] + 1), first_value=1)
    batches = [
        (0, t + 1) + traffic.mixed(kinds, sizes["batch"])
        for t in range(sizes["serial_phases"] + sizes["loop_k"])
    ]
    runs, seconds = {}, {}
    for backend in ("jnp", "pallas"):
        t0 = time.perf_counter()
        runs[backend] = _run_fabric(backend, sizes, tmp, batches)
        seconds[backend] = round(time.perf_counter() - t0, 3)
    (rt_j, out_j), (rt_p, out_p) = runs["jnp"], runs["pallas"]

    oracle = [{} if k == "map" else [] for k in kinds]
    checked = 0
    for i, entry in enumerate(batches):
        resp, kk = _reference(kinds, oracle, entry, sizes["lanes"],
                              sizes["capacity"])
        checked += _agree(*out_j[i], resp, kk, f"B jnp phase {i}")
        _agree(*out_p[i], resp, kk, f"B pallas phase {i}")
        if out_j[i] != out_p[i]:
            raise AssertionError(f"B phase {i}: jnp and pallas differ")
    items = _contents_agree(rt_j, oracle, "B jnp contents")
    _contents_agree(rt_p, oracle, "B pallas contents")
    if dict(rt_j.fs.stats) != dict(rt_p.fs.stats):
        raise AssertionError(
            f"B persistence counts differ: {rt_j.fs.stats} vs {rt_p.fs.stats}"
        )
    calls = _kernel_calls(rt_p, sizes)
    if min(calls.values()) < 4:  # one kernel per kind group, at least
        raise AssertionError(f"B pallas programs hold no Mosaic kernels: {calls}")
    log("B", seconds=seconds, tpu_custom_calls=calls,
        persist=dict(rt_p.fs.stats))
    return {"ops_checked": checked, "final_items": items}


# ------------------------------------------------------------------ phase C
def phase_c(sizes, tmp: Path):
    from repro.launch import serve

    state_dir = tmp / "serve"
    n = sizes["sessions"]
    t0 = time.perf_counter()
    serve.main([
        "--arch", "qwen2-1.5b", "--tier-only", "--durable",
        "--sessions", str(n), "--batch", str(sizes["session_batch"]),
        "--state-dir", str(state_dir), "--expect-exactly-once",
    ])
    served = serve._read_served(state_dir)
    serve.verify_exactly_once(range(1, n + 1), 0, served, {})
    log("C", sessions=n, served=len(served),
        seconds=round(time.perf_counter() - t0, 3))
    return {"sessions_served_once": len(served)}


PHASES = (("A", phase_a), ("B", phase_b), ("C", phase_c))


def main() -> int:
    if not (ROOT / "src" / "repro").is_dir():
        print("chip_smoke: the repro package (src/repro) is not next to this "
              "script", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    from repro.launch.compile_cache import use_compile_cache

    cache_dir = use_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX's first device is "
              f"{dev.platform!r})", file=sys.stderr)
        return 1
    compiles = CompileLog()
    log("setup", device_kind=dev.device_kind, devices=len(jax.devices()),
        jax=jax.__version__, compile_cache=cache_dir, sizes=SIZES)
    failed = []
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        for name, phase in PHASES:
            t0 = time.perf_counter()
            try:
                result = phase(SIZES, Path(tmp))
                status = "pass"
            except Exception:  # report, run the other phases, then fail
                traceback.print_exc()
                result, status = {}, "fail"
                failed.append(name)
            stats = dev.memory_stats() or {}
            log(
                name, status=status, wall_seconds=round(time.perf_counter() - t0, 3),
                compile_seconds=compiles.take(),
                device_bytes_in_use=stats.get("bytes_in_use"),
                device_peak_bytes=stats.get("peak_bytes_in_use"), **result,
            )
    log("cache", dir=cache_dir, **dict(compiles.events))
    if failed:
        print(f"chip_smoke: phase(s) {', '.join(failed)} failed", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
