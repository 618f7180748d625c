"""Driver of the serving tier (``system: tier``): an open loop of session
arrivals through ``RequestQueueTier``, with no model behind it.

Sessions are due at seeded times.  Each iteration submits every session that
is due, admits queued sessions up to the free decode slots, marks each
admitted session served and gives its slot back; batches are powers of two
(see ``_iteration``).  Admission latency runs from a session's due time to the
``admit`` call that returns it; that admission is durable (its dequeue and
its ADMITTED state are committed).

Every fabric phase the tier runs is logged at the fabric's entry points
(``announce`` and ``read_responses``) and replayed through the reference
afterwards; the tier is then crashed, recovered, and its queues, slot pool
and session map read back.
"""

from __future__ import annotations

import collections
import time
from typing import Dict, List

import numpy as np

from bench import durable, harness
from bench.reference import semantics as ref
from bench.reference import tier as tref
from bench.traffic import arrival_times

DRAIN_LIMIT_S = 60.0


class Driver:
    span_names = ("submit", "admit", "mark_served", "release")

    def __init__(self, config: Dict, traffic: Dict, ctx, log):
        self.cfg, self.traffic, self.ctx, self.log = config, traffic, ctx, log
        self.n_queues = int(config["queues"])
        self.slots = int(config["slots"])
        self.lanes = int(config["lanes"])
        self.capacity = int(config["capacity"])
        self.max_submit = int(traffic["max_submit"])
        self.phases: Dict[int, Dict] = {}  # token -> logged phase
        self.accepted: List[int] = []  # sessions whose enqueue was acknowledged
        self.admitted: List[int] = []  # admission order
        self.served: List[int] = []
        self.waiting: List[int] = []  # rejected for lanes, to submit again
        self.due: Dict[int, float] = {}  # sid -> due time (perf_counter)
        self.admit_t: Dict[int, float] = {}
        self.late: List[float] = []
        self.late_seen: set = set()
        self.attempted = 0
        self.failed = 0

    # ------------------------------------------------------------- set-up
    def setup(self) -> None:
        from repro.launch.serve import RequestQueueTier

        self.fs = durable.counting_fs(self.ctx.store / "tier")
        self.tier = RequestQueueTier(
            n_queues=self.n_queues, slots=self.slots, capacity=self.capacity,
            lanes=self.lanes, durable=True, fs=self.fs,
        )
        self._log_fabric(self.tier.rt)
        self._warm_shapes()
        warm = list(range(1, int(self.traffic["warmup_sessions"]) + 1))
        now = time.perf_counter()
        for sid in warm:
            self.due[sid] = now
        self.waiting = warm
        self._serve_all(time.perf_counter() + DRAIN_LIMIT_S)
        self.next_sid = len(warm) + 1

    def _log_fabric(self, rt) -> None:
        """Log every phase at the fabric's entry points: what was announced
        and the durable response record read back."""
        announce, read = rt.announce, rt.read_responses

        def logged_announce(thread, keys, ops, params, token):
            self.phases[token] = {"keys": np.asarray(keys, np.int64),
                                  "ops": np.asarray(ops, np.int64),
                                  "params": np.asarray(params, np.float64)}
            return announce(thread, keys, ops, params, token=token)

        def logged_read(thread, token=None, lane=None):
            val = read(thread, token=token, lane=lane)
            if val is not None and token in self.phases:
                self.phases[token]["resp"] = np.asarray(val["resp"], np.float64)
                self.phases[token]["kinds"] = np.asarray(val["kinds"], np.int64)
            return val

        rt.announce, rt.read_responses = logged_announce, logged_read

    def _warm_shapes(self) -> None:
        """Compile every phase size the loop can dispatch: the loop submits
        and admits in power-of-two batches, so a phase holds 2^k operations
        (submits hold two per session), up to twice ``max_submit``."""
        from repro.runtime.dfc_shard import ShardedDFCRuntime

        rt = self.tier.rt
        scratch = ShardedDFCRuntime(
            rt.kinds, rt.n_shards, self.lanes, self.lanes, n_buckets=rt.n_buckets,
            table=rt.table, fs=durable.counting_fs(self.ctx.store / "scratch"),
            n_threads=1,
        )
        sizes = [1 << k for k in range((2 * self.max_submit).bit_length())]
        durable.warm_batches(scratch, [[n] for n in sizes])
        durable.warm_step(rt, durable.pad_sizes(sizes[-1]))

    # ------------------------------------------------------------ the loop
    def _queued(self) -> int:
        return len(self.accepted) - len(self.admitted)

    def _serve_all(self, deadline: float) -> None:
        """Submit, admit and serve until nothing is waiting or queued, an
        admission finds nothing (a sound tier then has nothing left), or
        ``deadline`` passes."""
        while (self.waiting or self._queued()) and time.perf_counter() < deadline:
            admitted = len(self.admitted)
            self._iteration()
            if len(self.admitted) == admitted and not self.waiting:
                return

    def _iteration(self) -> bool:
        """Submit what is due, admit, serve, release; returns whether it did
        any work.  Batches are powers of two, so every phase has a size that
        set-up compiled: the due sessions go in chunks of the largest power
        of two that fits (at most ``max_submit``), and one admission takes
        the largest power of two of queued sessions that the slots hold."""
        span = self.ctx.spans
        batch, self.waiting = self.waiting, []
        worked = bool(batch)
        while batch:
            k = 1 << (min(len(batch), self.max_submit).bit_length() - 1)
            chunk, batch = batch[:k], batch[k:]
            t = time.perf_counter()
            self.late += [t - self.due[s] for s in chunk if s not in self.late_seen]
            self.late_seen.update(chunk)
            with span("submit"):
                rejected = set(self.tier.submit(chunk))
            self.accepted += [s for s in chunk if s not in rejected]
            self.waiting += [s for s in chunk if s in rejected]
        queued = self._queued()
        if queued:
            worked = True
            with span("admit"):
                pairs = self.tier.admit(1 << (min(self.slots, queued).bit_length() - 1))
            t = time.perf_counter()
            for sid, _ in pairs:
                self.admit_t[sid] = t
                self.admitted.append(sid)
            for sid, _ in pairs:
                with span("mark_served"):
                    self.tier.mark_served(sid)
                self.served.append(sid)
            if pairs:
                with span("release"):
                    self.tier.submit([], release_slots=[slot for _, slot in pairs])
        return worked

    def window(self, seconds: float) -> Dict:
        self.late_seen = set()
        self.late = []
        times = arrival_times(self.traffic, self.ctx.seed, seconds)
        sids = list(range(self.next_sid, self.next_sid + len(times)))
        self.window_sids = sids
        self.attempted = len(sids)
        self.counts0 = dict(self.fs.counts)
        admitted0 = len(self.admitted)
        t0 = time.perf_counter()
        for sid, t in zip(sids, times):
            self.due[sid] = t0 + float(t)
        i = 0
        while True:
            now = time.perf_counter()
            if now - t0 >= seconds:
                break
            while i < len(sids) and self.due[sids[i]] <= now:
                self.waiting.append(sids[i])
                i += 1
            if not self._iteration():
                nxt = self.due[sids[i]] if i < len(sids) else t0 + seconds
                time.sleep(max(0.0, min(nxt, t0 + seconds) - time.perf_counter()))
        self.elapsed = time.perf_counter() - t0
        self.counts1 = dict(self.fs.counts)
        self.window_admitted = len(self.admitted) - admitted0
        self.unsubmitted = sids[i:]
        return {"seconds": self.elapsed}

    def lateness(self) -> str:
        if not self.late:
            return "no submits"
        return (f"{len(self.late)} sessions submitted, p95 "
                f"{harness.percentile(self.late, 95) * 1e3:.3f} ms and max "
                f"{max(self.late) * 1e3:.3f} ms after due")

    # -------------------------------------------------------------- check
    def _drain(self) -> None:
        """Serve what the window left queued (latency keeps counting)."""
        self.waiting += self.unsubmitted
        self._serve_all(time.perf_counter() + DRAIN_LIMIT_S)

    def check(self) -> List[Dict]:
        self._drain()
        dtype = ref.bfloat16() if self.ctx.control else np.float32
        want = ref.Fabric(tref.kinds(self.n_queues), self.lanes, self.capacity,
                          table=tref.table(self.n_queues))
        got_ref = ref.Fabric(tref.kinds(self.n_queues), self.lanes, self.capacity,
                             table=tref.table(self.n_queues), dtype=dtype)
        pool = self.n_queues
        for fab in (want, got_ref):  # the constructor's slot seeding
            fab.shards[pool].extend(fab.cast(s) for s in range(self.slots))
        wrong = 0
        enq_order: Dict[int, List[int]] = collections.defaultdict(list)
        for token in sorted(self.phases):
            ph = self.phases[token]
            w_resp, w_kinds = want.phase(ph["keys"], ph["ops"], ph["params"])
            if self.ctx.control:
                g_resp, g_kinds = got_ref.phase(ph["keys"], ph["ops"], ph["params"])
            else:
                g_resp = ph.get("resp", np.full(len(ph["ops"]), np.nan))
                g_kinds = ph.get("kinds", np.full(len(ph["ops"]), -1))
            wrong += ref.mismatches(g_resp, g_kinds, w_resp, w_kinds)
            shards = want.route(ph["keys"])
            for j, op in enumerate(ph["ops"]):
                if (want.kinds[shards[j]] == "queue" and op == ref.OP_ENQ
                        and w_kinds[j] == ref.R_ACK):
                    enq_order[int(shards[j])].append(int(ph["params"][j]))
        # every request queue fed, FIFO within each, exactly once, all served
        unfed = sum(1 for q in range(self.n_queues) if not enq_order.get(q))
        shard_of = {sid: s for s, sids in enq_order.items() for sid in sids}
        adm_order: Dict[int, List[int]] = collections.defaultdict(list)
        for sid in self.admitted:
            adm_order[shard_of.get(sid, -1)].append(sid)
        fifo = sum(durable.list_mismatch(adm, enq_order.get(s, [])[:len(adm)])
                   for s, adm in adm_order.items())
        counts = collections.Counter(self.admitted)
        dup = sum(n - 1 for n in counts.values() if n > 1)
        lost = len(set(self.accepted) - set(counts)) + len(self.waiting)
        served = collections.Counter(self.served)
        not_served = sum(1 for sid in counts if served[sid] != 1)
        # read back: drop unsynced writes, recover the tier, compare
        from repro.launch.serve import RequestQueueTier

        del self.tier
        tier, _ = RequestQueueTier.recover(
            self.fs.crash(), n_queues=self.n_queues, capacity=self.capacity, lanes=self.lanes)
        readback = 0
        for s in range(tier.rt.n_shards):
            g = got_ref.contents(s) if self.ctx.control else tier.rt.shard_contents(s)
            if want.kinds[s] == "map":
                g = sorted((int(k), float(v)) for k, v in g)
            readback += durable.list_mismatch(g, want.contents(s))
        smap = dict(want.contents(self.n_queues + 1))
        unserved_state = sum(
            1 for sid in set(self.accepted)
            if int(smap.get(tref.state_key(sid, self.n_queues), -1)) % tref.STAGE_DOM != tref.SERVED
        )
        self.failed = sum(1 for sid in self.window_sids if sid not in self.admit_t)
        return [
            {"name": "responses_wrong", "value": wrong, "limit": 0},
            {"name": "queues_unfed", "value": unfed, "limit": 0},
            {"name": "fifo_wrong", "value": fifo, "limit": 0},
            {"name": "sessions_twice", "value": dup, "limit": 0},
            {"name": "sessions_lost", "value": lost, "limit": 0},
            {"name": "sessions_unserved", "value": not_served + unserved_state, "limit": 0},
            {"name": "readback_wrong", "value": readback, "limit": 0},
        ]

    # ------------------------------------------------------------ metrics
    def end_to_end(self) -> Dict[str, float]:
        waits = [self.admit_t[s] - self.due[s] for s in self.window_sids if s in self.admit_t]
        return {"admit_p95_ms": harness.percentile(waits, 95) * 1e3,
                "sessions_per_s": self.window_admitted / self.elapsed}

    def record(self, trace):
        return durable.record(
            spans=dict(self.ctx.spans.durations), trace=trace,
            counters=durable.counter_delta(self.counts1, self.counts0),
            sessions=self.window_admitted, device_kind=self.ctx.device_kind,
        )

