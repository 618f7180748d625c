"""Obs smoke: tracing must be a pure observer of the durable path.

The flight recorder's hard constraint (docs/observability.md): enabling
tracing may not change durable state or persistence counts by even one
operation.  This script is the CI gate for that claim.  It drives the SAME
fused phase-loop schedule twice — once untraced, once under a
``FabricObserver`` with a durable sidecar — and asserts:

  1. ``fs.stats`` (total pwb/pfence) identical;
  2. ``fs.pstats`` (per-tag pwb/pfence) identical;
  3. the durable-state digest (every byte under the root, obs/ excluded)
     identical;
  4. recovery over the traced root EXTENDS the sidecar with per-thread
     verdict events, with trace seq numbers monotone across the reboot;
  5. 1-3 hold as well for a drive under a ``jax.profiler`` session, which
     switches the program's spans and counters on;

then renders the ``fabric_top`` table from the sidecar as a smoke of the
operator tooling.  Exits non-zero on any violation.

Run:  python tools/obs_smoke.py  (CI runs it on every push)
"""

from __future__ import annotations

import shutil
import sys
import tempfile
from pathlib import Path

import numpy as np

_ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(_ROOT / "src"))
sys.path.insert(0, str(_ROOT / "tools"))

from repro.checkpoint.dfc_checkpoint import SimFS  # noqa: E402
from repro.obs import (  # noqa: E402
    EV_VERDICT,
    FabricObserver,
    durable_digest,
    profile_totals,
    read_trace,
)
from repro.runtime.dfc_shard import ShardedDFCRuntime  # noqa: E402

import fabric_top  # noqa: E402

KIND, N_SHARDS, BATCH, ROUNDS = "queue", 2, 8, 12
CAP = BATCH * (ROUNDS + 2)  # map-compatible too: 112 = 14 buckets of 8


def _schedule(seed=0):
    rng = np.random.default_rng(seed)
    return [
        (
            0,
            r + 1,
            rng.integers(0, 4096, BATCH),
            rng.integers(1, 3, BATCH),
            rng.random(BATCH).astype(np.float32),
        )
        for r in range(ROUNDS)
    ]


def _map_schedule(seed=1):
    """Mixed insert/lookup/delete/CAS rounds for the keyed-map case (CAS
    params pack expected*4096 + new)."""
    rng = np.random.default_rng(seed)
    sched = []
    for r in range(ROUNDS):
        ops = rng.integers(1, 5, BATCH)
        vals = rng.integers(0, 4096, BATCH).astype(np.float64)
        expect = rng.integers(0, 4096, BATCH)
        params = np.where(ops == 4, expect * 4096.0 + vals, vals)
        sched.append((0, r + 1, rng.integers(0, 4096, BATCH), ops, params))
    return sched


def _drive(root: Path, obs=None, kind=KIND, schedule=None):
    fs = SimFS(root)
    rt = ShardedDFCRuntime(
        kind, N_SHARDS, CAP, BATCH, fs=fs, n_threads=1, depth=2, obs=obs,
    )
    rt.phase_loop(schedule if schedule is not None else _schedule())
    if obs is not None:
        obs.observe_fabric(rt)
        obs.flush()
    return fs, rt


def _drive_profiled(root: Path, log_dir: Path):
    import jax

    jax.profiler.start_trace(str(log_dir))
    try:
        return _drive(root)
    finally:
        jax.profiler.stop_trace()


def main() -> int:
    base = Path(tempfile.mkdtemp(prefix="dfc_obs_smoke_"))
    failures = []
    try:
        fs_plain, _ = _drive(base / "plain")
        obs = FabricObserver(root=base / "traced")
        fs_traced, _ = _drive(base / "traced", obs=obs)

        if dict(fs_plain.stats) != dict(fs_traced.stats):
            failures.append(
                f"total pwb/pfence diverged: {dict(fs_plain.stats)} vs "
                f"{dict(fs_traced.stats)}"
            )
        if fs_plain.pstats.as_dict() != fs_traced.pstats.as_dict():
            failures.append(
                f"per-tag pwb/pfence diverged: {fs_plain.pstats.as_dict()} "
                f"vs {fs_traced.pstats.as_dict()}"
            )
        d_plain = durable_digest(base / "plain")
        d_traced = durable_digest(base / "traced")
        if d_plain != d_traced:
            failures.append(
                f"durable state diverged: {d_plain} vs {d_traced}"
            )

        # the program's spans and counters, switched on by a profiler
        # session, are as pure as the flight recorder
        fs_prof, _ = _drive_profiled(base / "profiled", base / "profile")
        if fs_plain.pstats.as_dict() != fs_prof.pstats.as_dict():
            failures.append(
                f"profiled: per-tag pwb/pfence diverged: "
                f"{fs_plain.pstats.as_dict()} vs {fs_prof.pstats.as_dict()}"
            )
        if d_plain != durable_digest(base / "profiled"):
            failures.append("profiled: durable state diverged")
        if "retire.fetch" not in profile_totals()["spans"]:
            failures.append("profiled: no retire.fetch span in the session's tally")

        # the purity invariant is gated on the keyed-map kind too: the same
        # insert/lookup/delete/CAS schedule traced and untraced
        fs_mplain, _ = _drive(
            base / "map_plain", kind="map", schedule=_map_schedule()
        )
        obs_map = FabricObserver(root=base / "map_traced")
        fs_mtraced, _ = _drive(
            base / "map_traced", obs=obs_map, kind="map",
            schedule=_map_schedule(),
        )
        if dict(fs_mplain.stats) != dict(fs_mtraced.stats):
            failures.append(
                f"map: total pwb/pfence diverged: {dict(fs_mplain.stats)} "
                f"vs {dict(fs_mtraced.stats)}"
            )
        if fs_mplain.pstats.as_dict() != fs_mtraced.pstats.as_dict():
            failures.append(
                f"map: per-tag pwb/pfence diverged: "
                f"{fs_mplain.pstats.as_dict()} vs "
                f"{fs_mtraced.pstats.as_dict()}"
            )
        d_mplain = durable_digest(base / "map_plain")
        d_mtraced = durable_digest(base / "map_traced")
        if d_mplain != d_mtraced:
            failures.append(
                f"map: durable state diverged: {d_mplain} vs {d_mtraced}"
            )
        print(fabric_top.render(read_trace(obs_map.trace_path)))
        print()

        # clean-reboot recovery must extend the same sidecar with verdicts
        pre = read_trace(obs.trace_path)
        obs2 = FabricObserver(root=base / "traced")
        fs2 = SimFS(base / "traced")
        _, report = ShardedDFCRuntime.recover(
            fs2, kind=KIND, n_shards=N_SHARDS, capacity=CAP, lanes=BATCH,
            n_threads=1, depth=2, obs=obs2,
        )
        post = read_trace(obs.trace_path)
        if len(post) <= len(pre):
            failures.append("recovery did not extend the trace sidecar")
        seqs = [e["seq"] for e in post]
        if seqs != sorted(seqs) or len(set(seqs)) != len(seqs):
            failures.append("trace seq numbers not strictly monotone")
        verdicts = [e for e in post if e["ev"] == EV_VERDICT]
        if not verdicts:
            failures.append("recovery emitted no verdict events")
        if report[0]["token"] != ROUNDS:
            failures.append(
                f"recovery surfaced token {report[0]['token']}, "
                f"expected {ROUNDS}"
            )

        print(fabric_top.render(post))
        print()
        for f in failures:
            print(f"FAIL {f}")
        if not failures:
            print(
                f"obs smoke OK: {len(post)} trace events, "
                f"{len(verdicts)} verdict(s), digests equal "
                f"({d_plain}), stats equal {dict(fs_plain.stats)}"
            )
        return 1 if failures else 0
    finally:
        shutil.rmtree(base, ignore_errors=True)


if __name__ == "__main__":
    raise SystemExit(main())
