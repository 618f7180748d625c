"""Plain sequential semantics of the durable fabric, kept with the benchmark.

This is the yardstick that decides ``correct``: it imports nothing from the
program under test (``src/repro``) and takes nothing the program made.  It
holds the router's hash, the Zipf key draw and, per structure, what one
combining phase of announced operations does to a shard:

* queue: dequeues drain the committed contents FIFO; once drained, the k-th
  surplus dequeue takes the k-th enqueue of the same phase; remaining
  enqueues append in announcement order; dequeues beyond all of these
  return EMPTY.
* stack: the k-th push pairs with the k-th pop (elimination), surplus pushes
  go on top in announcement order, surplus pops take from the top.
* deque: same-side pairs eliminate, then left surplus, then right surplus.
* map: lanes apply in announcement order (insert, lookup, delete, CAS of two
  packed 12-bit operands); an insert into a full bucket of 8 is rejected.

A phase routes each key to a shard with the multiplicative hash (optionally
through a bucket table) and gives each shard at most ``lanes`` operations in
announcement order; the rest overflow untouched.

``dtype`` is the payload precision the semantics compute in: float32 is what
the configurations state; the control run passes bfloat16.
"""

from __future__ import annotations

import collections
from typing import Dict, List, Sequence, Tuple

import numpy as np

HASH_MULT = 2654435761

# op codes: stack/queue (push == enq), deque, map
OP_NONE, OP_PUSH, OP_POP = 0, 1, 2
OP_ENQ, OP_DEQ = OP_PUSH, OP_POP
OP_PUSHL, OP_POPL, OP_PUSHR, OP_POPR = 1, 2, 3, 4
OP_MAP_INSERT, OP_MAP_LOOKUP, OP_MAP_DELETE, OP_MAP_CAS = 1, 2, 3, 4

# response kinds
R_NONE, R_ACK, R_VALUE, R_EMPTY, R_OVERFLOW, R_FULL, R_CAS_FAIL = range(7)

CAS_DOM = 4096
MAP_BUCKET_SLOTS = 8

# operation names per structure, as traffic files spell them
OP_NAMES = {
    "queue": {"enq": OP_ENQ, "deq": OP_DEQ},
    "stack": {"push": OP_PUSH, "pop": OP_POP},
    "deque": {"pushl": OP_PUSHL, "popl": OP_POPL,
              "pushr": OP_PUSHR, "popr": OP_POPR},
    "map": {"insert": OP_MAP_INSERT, "lookup": OP_MAP_LOOKUP,
            "delete": OP_MAP_DELETE, "cas": OP_MAP_CAS},
}
# operations that write their parameter into the structure
WRITES = {
    "queue": {OP_ENQ}, "stack": {OP_PUSH},
    "deque": {OP_PUSHL, OP_PUSHR}, "map": {OP_MAP_INSERT, OP_MAP_CAS},
}


# ------------------------------------------------------------------ routing
def bucket_of_keys(keys, n_buckets: int) -> np.ndarray:
    """Multiplicative hash of 32-bit keys, then one xor-shift."""
    k = np.asarray(keys).astype(np.uint32)
    h = k * np.uint32(HASH_MULT)
    h = h ^ (h >> np.uint32(16))
    return (h % np.uint32(n_buckets)).astype(np.int64)


def route(keys, n_shards: int, table=None) -> np.ndarray:
    """Shard of each key: the hash bucket, through ``table`` when given."""
    if table is None:
        return bucket_of_keys(keys, n_shards)
    table = np.asarray(table)
    return table[bucket_of_keys(keys, len(table))].astype(np.int64)


def keys_for_shards(shards: Sequence[int], n_shards: int, count: int,
                    table=None) -> np.ndarray:
    """The ``count`` smallest non-negative keys routing into ``shards``."""
    want = set(int(s) for s in shards)
    out: List[int] = []
    base = 0
    while len(out) < count:
        cand = np.arange(base, base + 65536, dtype=np.int64)
        hit = cand[np.isin(route(cand, n_shards, table), list(want))]
        out.extend(int(k) for k in hit[: count - len(out)])
        base += 65536
    return np.asarray(out, np.int64)


def zipf_cdf(universe: int, skew: float) -> np.ndarray:
    """Cumulative distribution of a Zipf law over ranks 1..universe."""
    ranks = np.arange(1, universe + 1, dtype=np.float64)
    p = ranks ** (-skew) if skew > 0 else np.ones(universe)
    cdf = np.cumsum(p)
    return cdf / cdf[-1]


def zipf_draw(rng, cdf: np.ndarray, n: int) -> np.ndarray:
    """``n`` Zipf-distributed indices into the universe of ``cdf``."""
    return np.minimum(np.searchsorted(cdf, rng.random(n), side="right"),
                      cdf.shape[0] - 1).astype(np.int64)


# ---------------------------------------------------------------- per kind
def bfloat16():
    """The bfloat16 dtype, the precision below the float32 payloads."""
    import ml_dtypes

    return ml_dtypes.bfloat16


def _cast(dtype):
    return lambda v: float(np.asarray(v, np.float32).astype(dtype))


def queue_phase(q: collections.deque, ops, params, cast):
    n = len(ops)
    resp, kinds = [0.0] * n, [R_NONE] * n
    enqs = [i for i in range(n) if ops[i] == OP_ENQ]
    deqs = [i for i in range(n) if ops[i] == OP_DEQ]
    for i in enqs:
        kinds[i] = R_ACK
    di = 0
    while di < len(deqs) and q:
        resp[deqs[di]] = q.popleft()
        kinds[deqs[di]] = R_VALUE
        di += 1
    ei = 0
    while di < len(deqs) and ei < len(enqs):
        resp[deqs[di]] = cast(params[enqs[ei]])
        kinds[deqs[di]] = R_VALUE
        di += 1
        ei += 1
    for i in deqs[di:]:
        kinds[i] = R_EMPTY
    for i in enqs[ei:]:
        q.append(cast(params[i]))
    return resp, kinds


def stack_phase(st: list, ops, params, cast):
    n = len(ops)
    resp, kinds = [0.0] * n, [R_NONE] * n
    pushes = [i for i in range(n) if ops[i] == OP_PUSH]
    pops = [i for i in range(n) if ops[i] == OP_POP]
    e = min(len(pushes), len(pops))
    for k in range(e):
        kinds[pushes[k]] = R_ACK
        kinds[pops[k]] = R_VALUE
        resp[pops[k]] = cast(params[pushes[k]])
    for i in pushes[e:]:
        st.append(cast(params[i]))
        kinds[i] = R_ACK
    for i in pops[e:]:
        if st:
            resp[i] = st.pop()
            kinds[i] = R_VALUE
        else:
            kinds[i] = R_EMPTY
    return resp, kinds


def deque_phase(d: collections.deque, ops, params, cast):
    n = len(ops)
    resp, kinds = [0.0] * n, [R_NONE] * n
    side = {c: [i for i in range(n) if ops[i] == c]
            for c in (OP_PUSHL, OP_POPL, OP_PUSHR, OP_POPR)}
    pl, ql, pr, qr = (side[c] for c in (OP_PUSHL, OP_POPL, OP_PUSHR, OP_POPR))
    nl, nr = min(len(pl), len(ql)), min(len(pr), len(qr))
    for push, pop, m in ((pl, ql, nl), (pr, qr, nr)):
        for k in range(m):
            kinds[push[k]] = R_ACK
            kinds[pop[k]] = R_VALUE
            resp[pop[k]] = cast(params[push[k]])
    for i in pl[nl:]:
        d.appendleft(cast(params[i]))
        kinds[i] = R_ACK
    for i in ql[nl:]:
        if d:
            resp[i], kinds[i] = d.popleft(), R_VALUE
        else:
            kinds[i] = R_EMPTY
    for i in pr[nr:]:
        d.append(cast(params[i]))
        kinds[i] = R_ACK
    for i in qr[nr:]:
        if d:
            resp[i], kinds[i] = d.pop(), R_VALUE
        else:
            kinds[i] = R_EMPTY
    return resp, kinds


def map_bucket(keys, n_buckets: int) -> np.ndarray:
    """A map shard's bucket of each key (a second mix, independent of the
    router's)."""
    h = (np.asarray(keys, np.uint64) * 2654435761) & 0xFFFFFFFF
    h = h ^ (h >> 16)
    h = (h * 2246822519) & 0xFFFFFFFF
    h = h ^ (h >> 13)
    return (h % n_buckets).astype(np.int64)


def map_phase(m: dict, keys, ops, params, cast, capacity: int):
    n = len(ops)
    resp, kinds = [0.0] * n, [R_NONE] * n
    bslots = min(capacity, MAP_BUCKET_SLOTS)
    n_buckets = capacity // bslots
    fill = collections.Counter(int(b) for b in map_bucket(list(m), n_buckets))
    for i in range(n):
        op, key, par = int(ops[i]), int(keys[i]), cast(params[i])
        if op == OP_MAP_INSERT:
            if key not in m:
                b = int(map_bucket([key], n_buckets)[0])
                if fill[b] >= bslots:
                    kinds[i] = R_FULL
                    continue
                fill[b] += 1
            m[key] = par
            kinds[i] = R_ACK
        elif op in (OP_MAP_LOOKUP, OP_MAP_DELETE, OP_MAP_CAS):
            if key not in m:
                kinds[i] = R_EMPTY
                continue
            resp[i] = m[key]
            if op == OP_MAP_LOOKUP:
                kinds[i] = R_VALUE
            elif op == OP_MAP_DELETE:
                del m[key]
                fill[int(map_bucket([key], n_buckets)[0])] -= 1
                kinds[i] = R_VALUE
            else:
                p32 = np.float32(par)
                expected = float(np.floor(p32 / np.float32(CAS_DOM)))
                new = float(p32 - np.float32(expected) * np.float32(CAS_DOM))
                if m[key] == expected:
                    m[key] = cast(new)
                    kinds[i] = R_VALUE
                else:
                    kinds[i] = R_CAS_FAIL
    return resp, kinds


def empty_shard(kind: str):
    return {"queue": collections.deque, "deque": collections.deque,
            "stack": list, "map": dict}[kind]()


class Fabric:
    """The plain reference of a sharded fabric: per-shard Python contents,
    advanced one combining phase at a time."""

    def __init__(self, kinds: Sequence[str], lanes: int, capacity: int,
                 table=None, dtype=np.float32):
        self.kinds = list(kinds)
        self.lanes = int(lanes)
        self.capacity = int(capacity)
        self.table = None if table is None else np.asarray(table)
        self.shards = [empty_shard(k) for k in self.kinds]
        self.cast = _cast(dtype)

    def route(self, keys) -> np.ndarray:
        return route(keys, len(self.kinds), self.table)

    def phase(self, keys, ops, params) -> Tuple[List[float], List[int]]:
        """One combining phase over a flat batch in announcement order;
        returns (responses, kinds) in batch order."""
        keys = np.asarray(keys, np.int64)
        ops = [int(o) for o in ops]
        shard = self.route(keys) if len(keys) else np.zeros(0, np.int64)
        resp, kinds = [0.0] * len(ops), [R_NONE] * len(ops)
        groups: Dict[int, List[int]] = collections.defaultdict(list)
        for j, op in enumerate(ops):
            if op == OP_NONE:
                continue
            g = groups[int(shard[j])]
            if len(g) >= self.lanes:
                kinds[j] = R_OVERFLOW
            else:
                g.append(j)
        for s, idx in sorted(groups.items()):
            s_ops = [ops[j] for j in idx]
            s_par = [params[j] for j in idx]
            kind = self.kinds[s]
            if kind == "map":
                r, k = map_phase(self.shards[s], [int(keys[j]) for j in idx],
                                 s_ops, s_par, self.cast, self.capacity)
            else:
                fn = {"queue": queue_phase, "stack": stack_phase,
                      "deque": deque_phase}[kind]
                r, k = fn(self.shards[s], s_ops, s_par, self.cast)
            for j, rv, kv in zip(idx, r, k):
                resp[j], kinds[j] = rv, kv
        return resp, kinds

    def contents(self, s: int):
        """Shard ``s`` as the fabric lists it: bottom-to-top / front-to-back
        values, or sorted (key, value) pairs of a map."""
        if self.kinds[s] == "map":
            return sorted((int(k), float(v)) for k, v in self.shards[s].items())
        return [float(v) for v in self.shards[s]]


def mismatches(got_resp, got_kinds, want_resp, want_kinds) -> int:
    """Operations whose (response, kind) differ from the reference's."""
    gr = np.asarray(got_resp, np.float64)
    wr = np.asarray(want_resp, np.float64)
    gk = np.asarray(got_kinds, np.int64)
    wk = np.asarray(want_kinds, np.int64)
    if gr.shape != wr.shape or gk.shape != wk.shape:
        return max(len(wk), len(gk))
    return int(np.count_nonzero((gr != wr) | (gk != wk)))
