"""Driver of the durable fabric (``system: fabric``): a closed loop of clients
through ``ShardedDFCRuntime``.

Each phase, every client announces its batch (``announce``), the combiner
runs one durable phase (``combine_phase``: fused device step, host drain,
one pfence, per-shard epoch commits) and every client reads its durable
response record back (``read_responses``).  A request is one client's
batch, timed from its ``announce`` call to its ``read_responses`` returning.

The fabric starts from a durable image holding the configuration's backlog:
built once per checkout under ``bench/.cache/`` (keyed by a digest of the
program and the configuration), copied into a fresh store each run and
opened with ``ShardedDFCRuntime.recover``.

With ``crash_every: n`` in the traffic, every n-th phase crashes at a seeded
persistence op: the store drops its unsynced writes, the fabric is recovered
and its pending operations replayed, and clients whose announcement never
became durable announce again.
"""

from __future__ import annotations

import hashlib
import json
import math
import os
import shutil
import time
from pathlib import Path
from typing import Dict, List

import numpy as np

from bench import durable, harness
from bench.reference import semantics as ref
from bench.traffic import ClosedLoop, crash_points

PREFILL_K = 16  # pre-fill phases per fused ``phase_loop`` dispatch of the image build


def program_digest(config: Dict) -> str:
    """Digest of the program's sources and the configuration."""
    h = hashlib.sha256(json.dumps(config, sort_keys=True).encode())
    src = harness.ROOT / "src" / "repro"
    for path in sorted(p for p in src.rglob("*") if p.is_file() and "__pycache__" not in p.parts):
        h.update(str(path.relative_to(src)).encode())
        h.update(path.read_bytes())
    return h.hexdigest()[:20]


class Driver:
    span_names = ("announce", "combine_phase", "read_responses", "recover",
                  "replay_pending", "redrive")

    def __init__(self, config: Dict, traffic: Dict, ctx, log):
        self.cfg, self.traffic, self.ctx, self.log = config, traffic, ctx, log
        self.kinds = [config["kind"]] * int(config["shards"])
        self.n_shards = len(self.kinds)
        self.capacity = int(config["capacity"])
        self.lanes = int(config["lanes"])
        self.threads = int(config["threads"])
        self.clients = int(traffic["clients"])
        if self.clients > self.threads:
            raise ValueError(f"{self.clients} clients need as many announcement threads")
        self.crash_every = int(traffic.get("crash_every", 0))
        self.phases: List[Dict] = []  # every combined phase, in commit order
        self.latencies: List[float] = []
        self.recoveries: List[tuple] = []
        self.acked = 0
        self.attempted = 0
        self.failed = 0
        self.last_phase_ops = None

    # ------------------------------------------------------------ fabric
    def _open(self, fs):
        from repro.runtime.dfc_shard import ShardedDFCRuntime

        return ShardedDFCRuntime.recover(
            fs, kind=self.kinds, n_shards=self.n_shards, capacity=self.capacity,
            lanes=self.lanes, n_threads=self.threads, depth=int(self.cfg["depth"]),
            backend=self.cfg["backend"],
        )

    def _backlog(self) -> List[List[float]]:
        """The configuration's backlog per shard: values 1..N in pre-fill
        order, ``lanes`` items per shard per pre-fill phase."""
        per_phase = self.n_shards * self.lanes
        n = int(self.cfg["backlog"])
        if n % per_phase:
            raise ValueError(f"backlog {n} is not a whole number of {per_phase}-item phases")
        out: List[List[float]] = [[] for _ in range(self.n_shards)]
        for p in range(n // per_phase):
            for s in range(self.n_shards):
                first = 1 + p * per_phase + s * self.lanes
                out[s].extend(float(v) for v in range(first, first + self.lanes))
        return out

    def _image(self) -> Path:
        """The durable image holding the backlog; built when absent."""
        root = self.ctx.cache_dir / "images" / f"{self.cfg['name']}-{program_digest(self.cfg)}"
        if (root / "image.json").exists():
            return root
        from repro.runtime.dfc_shard import ShardedDFCRuntime

        build = root.with_name(root.name + f".build{os.getpid()}")
        shutil.rmtree(build, ignore_errors=True)
        t0 = time.perf_counter()
        fs = durable.counting_fs(build / "store")
        rt = ShardedDFCRuntime(
            self.kinds, self.n_shards, self.capacity, self.lanes, fs=fs,
            n_threads=self.threads, depth=int(self.cfg["depth"]), backend=self.cfg["backend"],
        )
        write_op = min(ref.WRITES[self.cfg["kind"]])
        keys = np.repeat(self._shard_keys(), self.lanes)
        per_phase = keys.size
        n_phases = int(self.cfg["backlog"]) // per_phase
        sched = [
            (0, p + 1, keys, np.full(per_phase, write_op),
             np.arange(1 + p * per_phase, 1 + (p + 1) * per_phase, dtype=np.float64))
            for p in range(n_phases)
        ]
        for i in range(0, len(sched), PREFILL_K):
            rt.phase_loop(sched[i:i + PREFILL_K])
        del rt
        self._open(fs.crash())  # settle the epochs the image is copied with
        (build / "image.json").write_text(json.dumps({"next_token": n_phases + 1}))
        shutil.rmtree(root, ignore_errors=True)
        os.replace(build, root)
        self.log(f"image: built {n_phases} pre-fill phases in "
                 f"{time.perf_counter() - t0:.3f} s at {root}")
        return root

    def _shard_keys(self) -> np.ndarray:
        """The smallest key routing to each shard, shard order."""
        out = []
        for s in range(self.n_shards):
            out.append(int(ref.keys_for_shards([s], self.n_shards, 1)[0]))
        return np.asarray(out, np.int64)

    # ------------------------------------------------------------- set-up
    def setup(self) -> None:
        image = self._image()
        store = self.ctx.store / "fabric"
        shutil.copytree(image / "store", store)
        self.fs = durable.counting_fs(store)
        self.rt, _ = self._open(self.fs)
        self.token = json.loads((image / "image.json").read_text())["next_token"]
        self.gen = ClosedLoop(self.traffic, self.cfg, self.ctx.seed,
                              first_value=int(self.cfg["backlog"]) + 1)
        if self.crash_every:
            self.crash_at = crash_points(self.traffic, self.ctx.seed)
            self._warm_replay_shapes()
        for _ in range(int(self.traffic["warmup_phases"])):
            self._step(in_window=False)
        if self.crash_every:  # one crash cycle, so recovery's programs are warm
            while not self._step(in_window=False, crash=True):
                pass
            self._step(in_window=False)

    def _warm_replay_shapes(self) -> None:
        """Compile what a replay after a crash dispatches where its shapes
        can be known: the first j clients' whole batches (a crash among the
        announcements), and the fused step at every padded width up to a
        whole phase.  A crash between two shards' epoch commits replays a
        different subset of each client's batch; those shapes are new."""
        from repro.runtime.dfc_shard import ShardedDFCRuntime

        scratch = ShardedDFCRuntime(
            self.kinds, self.n_shards, self.lanes, self.lanes,
            fs=durable.counting_fs(self.ctx.store / "scratch"), n_threads=self.clients,
        )
        per_client = int(self.traffic["ops_per_client"])
        durable.warm_batches(scratch, [[per_client] * j for j in range(1, self.clients + 1)])
        durable.warm_step(self.rt, durable.pad_sizes(self.clients * per_client))

    # ------------------------------------------------------------ a phase
    def _step(self, in_window: bool, crash: bool = False) -> bool:
        """One phase of the closed loop; returns whether it crashed."""
        from repro.checkpoint.dfc_checkpoint import CrashNow

        span = self.ctx.spans
        batches = self.gen.phase()
        self.token += 1
        tok = self.token
        if crash:
            ops = self.last_phase_ops or 1
            self.fs.injector.crash_at = self.fs.injector.count + max(
                1, int(self.crash_at.take() * ops))
        count0 = self.fs.injector.count
        start = []
        try:
            for c, (keys, ops, params) in enumerate(batches):
                start.append(time.perf_counter())
                with span("announce"):
                    self.rt.announce(c, keys, ops, params, token=tok)
            with span("combine_phase"):
                self.rt.combine_phase()
            got = []
            for c in range(self.clients):
                with span("read_responses"):
                    val = self.rt.read_responses(c, token=tok)
                if val is None:
                    raise RuntimeError(f"client {c} token {tok}: no durable response "
                                       "after its phase")
                got.append(val)
                if in_window:
                    self.latencies.append(time.perf_counter() - start[c])
        except CrashNow:
            self._recover(batches, tok, in_window)
            return True
        self.fs.injector.crash_at = None
        self.last_phase_ops = self.fs.injector.count - count0
        self._log_phase(batches, range(self.clients), got, in_window)
        if in_window:
            n = sum(len(b[1]) for b in batches)
            self.attempted += n
            self.acked += n
        return False

    def _log_phase(self, batches, clients, got, in_window) -> None:
        clients = list(clients)
        if not clients:
            return
        self.phases.append({
            "segs": [batches[c] for c in clients],
            "resp": np.concatenate([np.asarray(g["resp"], np.float64) for g in got])
            if got else np.zeros(0),
            "kinds": np.concatenate([np.asarray(g["kinds"], np.int64) for g in got])
            if got else np.zeros(0, np.int64),
            "window": in_window,
        })

    def _recover(self, batches, tok, in_window) -> None:
        """After a crash: recover, replay what was pending, re-drive the
        clients whose announcement never became durable, and log what each
        operation was answered (applied verdict or replayed response)."""
        span = self.ctx.spans
        t0 = time.perf_counter()
        self.fs = self.fs.crash()
        del self.rt
        with span("recover"):
            self.rt, report = self._open(self.fs)
        t1 = time.perf_counter()
        with span("replay_pending"):
            replayed = set(self.rt.replay_pending(report))
        t2 = time.perf_counter()
        if in_window:
            self.recoveries.append((t1 - t0, t2 - t1))
        announced = [c for c in range(self.clients) if report[c]["token"] == tok]
        got = []
        for c in announced:
            verdicts = report[c]["ops"]
            resp = [v.resp if v.applied else math.nan for v in verdicts]
            kinds = [v.kind if v.applied else -1 for v in verdicts]
            if c in replayed:
                val = self.rt.read_responses(c, token=tok)
                redo = [i for i, v in enumerate(verdicts)
                        if not v.applied and v.kind != ref.R_NONE]
                for j, i in enumerate(redo):
                    resp[i], kinds[i] = val["resp"][j], val["kinds"][j]
            got.append({"resp": resp, "kinds": kinds})
        self._log_phase(batches, announced, got, in_window)
        rest = [c for c in range(self.clients) if c not in announced]
        if rest:
            with span("redrive"):
                for c in rest:
                    self.rt.announce(c, *batches[c], token=tok)
                self.rt.combine_phase()
                got = [self.rt.read_responses(c, token=tok) for c in rest]
            self._log_phase(batches, rest, got, in_window)

    # ------------------------------------------------------------- window
    def window(self, seconds: float) -> Dict:
        self.counts0 = dict(self.fs.counts)
        n = 0
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            crash = bool(self.crash_every) and n % self.crash_every == self.crash_every - 1
            self._step(in_window=True, crash=crash)
            n += 1
        self.elapsed = time.perf_counter() - t0
        self.counts1 = dict(self.fs.counts)
        self.window_phases = n
        return {"seconds": self.elapsed}

    def lateness(self) -> str:
        return "none (closed loop: each client waits for its response)"

    # -------------------------------------------------------------- check
    def _reference(self, dtype):
        fab = ref.Fabric(self.kinds, self.lanes, self.capacity, dtype=dtype)
        for s, values in enumerate(self._backlog()):
            fab.shards[s].extend(fab.cast(v) for v in values)
        return fab

    def check(self) -> List[Dict]:
        want = self._reference(np.float32)
        control = self._reference(ref.bfloat16()) if self.ctx.control else None
        wrong = 0
        for ph in self.phases:
            keys, ops, params = (np.concatenate([s[i] for s in ph["segs"]]) for i in range(3))
            w_resp, w_kinds = want.phase(keys, ops, params)
            if control is not None:
                g_resp, g_kinds = control.phase(keys, ops, params)
            else:
                g_resp, g_kinds = ph["resp"], ph["kinds"]
            wrong += ref.mismatches(g_resp, g_kinds, w_resp, w_kinds)
        # read back: drop unsynced writes, recover, compare every shard
        self.fs = self.fs.crash()
        del self.rt
        rt, _ = self._open(self.fs)
        readback = 0
        for s in range(self.n_shards):
            got = (control.contents(s) if control is not None else rt.shard_contents(s))
            readback += durable.list_mismatch(got, want.contents(s))
        return [
            {"name": "responses_wrong", "value": wrong, "limit": 0},
            {"name": "readback_wrong", "value": readback, "limit": 0},
        ]

    # ------------------------------------------------------------ metrics
    def end_to_end(self) -> Dict[str, float]:
        out = {"ops_per_s": self.acked / self.elapsed}
        if self.latencies:
            out["p95_ms"] = harness.percentile(self.latencies, 95) * 1e3
        if self.recoveries:
            out["recover_s"] = sum(a + b for a, b in self.recoveries) / len(self.recoveries)
        return out

    def record(self, trace):
        window = [ph for ph in self.phases if ph["window"]]
        return durable.record(
            spans=dict(self.ctx.spans.durations), trace=trace,
            counters=durable.counter_delta(self.counts1, self.counts0),
            phases=self.window_phases, acked_ops=self.acked,
            crashes=len(self.recoveries),
            phase_ops=[
                (np.concatenate([s[1] for s in ph["segs"]]), ph["kinds"],
                 ref.route(np.concatenate([s[0] for s in ph["segs"]]), self.n_shards))
                for ph in window
            ],
            kind=self.cfg["kind"], device_kind=self.ctx.device_kind,
        )

