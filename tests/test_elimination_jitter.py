"""Elimination-aware batching regression: the queue eliminates only when
drained (ROADMAP item; paper Figure 3 discussion).

The stack's combiner eliminates concurrent push/pop pairs regardless of the
committed state, so a balanced batch touches no storage — with the durable
path's dirty-leaf elision, only the root counters and epoch re-persist.
The FIFO queue can only pair a dequeue with a concurrent enqueue once the
committed window is DRAINED: under arrival jitter (producers running ahead
of consumers by some think-time lag), a standing backlog forms, every
dequeue is served from the ring, every enqueue appends — and the values
array is dirty every phase.

The test drives the same balanced workload through one-shard stack and
queue fabrics at lag 0 (no jitter: both fully eliminate, measured pwb/op
equal) and at lag > 0 (jitter: queue strictly worse), asserting the
pwb/op ordering queue >= stack that the paper's Figure 3 predicts.
"""

import tempfile
from pathlib import Path

import pytest

import jax

from repro.checkpoint.dfc_checkpoint import SimFS
from repro.core.jax_dfc import OP_POP, OP_PUSH
from repro.runtime.dfc_shard import ShardedDFCRuntime

jax.config.update("jax_platform_name", "cpu")

CAP, LANES = 256, 32
M = 8  # balanced ops per side per phase
PHASES = 6


def _pwb_per_op(kind: str, lag: int) -> float:
    """Measured pwb/op of ``PHASES`` balanced (M pushes + M pops) phases on a
    one-shard ``kind`` fabric whose producers run ``lag`` values ahead of
    consumers (the arrival think-time backlog).  Only the steady-state
    balanced phases are measured — the prefill that models the jitter lag is
    excluded, as is the first measured phase (cold persist of every leaf)."""
    fs = SimFS(Path(tempfile.mkdtemp(prefix=f"dfc_jitter_{kind}_")))
    rt = ShardedDFCRuntime(kind, 1, CAP, LANES, fs=fs, n_threads=1)
    token = 0
    key = rt.key_for_shard(0)

    def phase(ops, params):
        nonlocal token
        token += 1
        rt.announce(0, [key] * len(ops), ops, params, token=token)
        rt.combine_phase()

    if lag:
        phase([OP_PUSH] * lag, [100.0 + i for i in range(lag)])
    # one warm-up balanced phase: first write of each leaf into each slot
    phase([OP_PUSH] * M + [OP_POP] * M, [float(i) for i in range(2 * M)])
    phase([OP_PUSH] * M + [OP_POP] * M, [float(i) for i in range(2 * M)])
    base = dict(fs.stats)
    for p in range(PHASES):
        phase(
            [OP_PUSH] * M + [OP_POP] * M,
            [10.0 * p + i for i in range(2 * M)],
        )
    ops_measured = PHASES * 2 * M
    return (fs.stats["pwb"] - base["pwb"]) / ops_measured


def test_queue_eliminates_only_when_drained():
    """Figure-3 ordering: under jitter (standing backlog) the queue pays
    strictly more pwb/op than the stack; drained (lag 0) they tie."""
    stack_0 = _pwb_per_op("stack", lag=0)
    queue_0 = _pwb_per_op("queue", lag=0)
    stack_j = _pwb_per_op("stack", lag=3 * M)
    queue_j = _pwb_per_op("queue", lag=3 * M)

    # the paper's predicted ordering: queue >= stack, strict under jitter
    assert queue_0 >= stack_0
    assert queue_j > stack_j, (
        f"queue ({queue_j:.3f}) should pay more pwb/op than the stack "
        f"({stack_j:.3f}) when arrival jitter keeps it un-drained"
    )
    # drained, both structures fully eliminate: identical persist schedules
    assert queue_0 == pytest.approx(stack_0)
    # jitter costs the QUEUE extra persistence, not the stack
    assert queue_j > queue_0
    assert stack_j == pytest.approx(stack_0)


def _queue_lane_cost(split: bool, skewed: bool) -> dict:
    """Measured steady-state pwb/op AND pfence/op of a one-shard queue
    fabric, one-lane (``split=False``) or two-lane (``split=True``).

    ``skewed=True`` models arrival skew: a standing backlog (producers
    ``3*M`` ahead) with alternating tail-only enqueue bursts and head-only
    dequeue bursts — each burst is a single-lane phase, so the split fabric
    commits just that side's record and epoch.  ``skewed=False`` is the
    drained balanced workload: every phase fully eliminates, so neither
    layout persists values (a drained balanced phase is a handoff: the
    split fabric writes both lane records)."""
    fs = SimFS(Path(tempfile.mkdtemp(prefix=f"dfc_lanejit_{int(split)}_")))
    rt = ShardedDFCRuntime(
        "queue", 1, CAP, LANES, fs=fs, n_threads=1, split_lanes=split
    )
    token = 0
    key = rt.key_for_shard(0)

    def phase(ops, params):
        nonlocal token
        token += 1
        rt.announce(0, [key] * len(ops), ops, params, token=token)
        rt.combine_phase()

    def burst_pair(p):
        phase([OP_PUSH] * M, [100.0 * p + i for i in range(M)])
        phase([OP_POP] * M, [0.0] * M)

    if skewed:
        phase([OP_PUSH] * (3 * M), [float(i) for i in range(3 * M)])  # lag
        burst_pair(1)  # warm-up: cold persist of every leaf, both slots
        burst_pair(2)
        base = dict(fs.stats)
        for p in range(PHASES):
            burst_pair(10 + p)
    else:
        for p in (1, 2):  # warm-up
            phase([OP_PUSH] * M + [OP_POP] * M,
                  [float(i) for i in range(2 * M)])
        base = dict(fs.stats)
        for p in range(PHASES):
            phase([OP_PUSH] * M + [OP_POP] * M,
                  [10.0 * p + i for i in range(2 * M)])
    ops_measured = PHASES * 2 * M
    return {
        "pwb": (fs.stats["pwb"] - base["pwb"]) / ops_measured,
        "pfence": (fs.stats["pfence"] - base["pfence"]) / ops_measured,
    }


def test_split_lanes_beat_one_lane_under_skew():
    """Per-side combiners (ISSUE 8) against the one-lane layout, pinned as
    exact CPU counts.  Under arrival skew a two-lane queue commits only the
    active side per phase, so it still pays fewer pwb/op, but only by one
    values file a burst pair: the one-lane dequeue phase re-writes values
    into the slot that last held them before the enqueue burst, where the
    head lane writes no values.  The one-lane slot no longer pays for its
    counter pair and epoch, which ride inline in ``meta.json``.  Drained,
    the balanced workload fully eliminates; every phase is a handoff that
    writes both lane records, so the one-lane layout (one manifest) pays
    less.  The pfence schedule is identical in both mixes."""
    one_skew = _queue_lane_cost(split=False, skewed=True)
    two_skew = _queue_lane_cost(split=True, skewed=True)
    one_drained = _queue_lane_cost(split=False, skewed=False)
    two_drained = _queue_lane_cost(split=True, skewed=False)

    # per 16-op burst pair: 6 announce + 2 values + 2 manifests + 2
    # responses + 4 epoch writes (one-lane); the dequeue phase's head lane
    # writes one rec.json and no values (two-lane)
    assert one_skew == {"pwb": 1.0, "pfence": 0.5}
    assert two_skew == {"pwb": 0.9375, "pfence": 0.5}
    # per 16-op drained phase: 3 announce + 1 manifest + 1 response + 2
    # epoch writes (one-lane); both lane records, no values (two-lane)
    assert one_drained == {"pwb": 0.4375, "pfence": 0.25}
    assert two_drained == {"pwb": 0.5, "pfence": 0.25}


def test_stack_elides_untouched_values_leaf():
    """Mechanism check for the measurement above: a fully-eliminating stack
    phase re-persists epoch + manifest but NOT the untouched values array
    (dirty-leaf elision), while a surplus push dirties it again."""
    fs = SimFS(Path(tempfile.mkdtemp(prefix="dfc_elide_")))
    rt = ShardedDFCRuntime("stack", 1, CAP, LANES, fs=fs, n_threads=1)
    key = rt.key_for_shard(0)
    rt.announce(0, [key] * 4, [OP_PUSH] * 4, [1.0, 2.0, 3.0, 4.0], token=1)
    rt.combine_phase()
    # two balanced phases: same slot written twice with identical values
    for tok in (2, 3):
        rt.announce(0, [key, key], [OP_PUSH, OP_POP], [9.0, 0.0], token=tok)
        rt.combine_phase()
    before = fs.stats["pwb"]
    rt.announce(0, [key, key], [OP_PUSH, OP_POP], [9.0, 0.0], token=4)
    rt.combine_phase()
    balanced_cost = fs.stats["pwb"] - before
    before = fs.stats["pwb"]
    rt.announce(0, [key], [OP_PUSH], [5.0], token=5)
    rt.combine_phase()
    surplus_cost = fs.stats["pwb"] - before
    assert surplus_cost > balanced_cost  # the values leaf is dirty again
    # crash safety: elision never leaves a slot unreadable
    rt2, _ = ShardedDFCRuntime.recover(
        fs.crash(), kind="stack", n_shards=1, capacity=CAP, lanes=LANES,
        n_threads=1,
    )
    assert rt2.shard_contents(0) == [1.0, 2.0, 3.0, 4.0, 5.0]
