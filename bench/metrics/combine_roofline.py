"""Share (%) of the chip's memory-bandwidth roofline reached by the fused
combine program (``hetero_multi_step``) over the traced window.

The least bytes a phase's operations require, whatever implements them:
12 B per op in (key, op, param), 8 B per op out (response, kind), 4 B per
payload slot written (an enqueue / push / insert) or read (a dequeue / pop
that returns a value), and 16 B of root counters per shard the phase
touches.  Divided by the HBM bandwidth of ``bench/peaks.json``, that is the
least time; divided by the program's device time in the trace, the share.
"""

import json
from pathlib import Path

import numpy as np

IN_BYTES, OUT_BYTES, SLOT_BYTES, ROOT_BYTES = 12, 8, 4, 16
R_VALUE = 2
WRITES = {"queue": (1,), "stack": (1,), "deque": (1, 3), "map": (1, 4)}


def phase_bytes(kind: str, ops, resp_kinds, shards) -> int:
    """Least bytes one phase of ``ops`` (with their response kinds and
    target shards) moves."""
    ops = np.asarray(ops)
    live = ops != 0
    n = int(live.sum())
    written = int(np.isin(ops, WRITES[kind]).sum())
    read_back = int(((np.asarray(resp_kinds) == R_VALUE) & live
                     & ~np.isin(ops, WRITES[kind])).sum())
    touched = len(set(np.asarray(shards)[live].tolist()))
    return (IN_BYTES + OUT_BYTES) * n + SLOT_BYTES * (written + read_back) + ROOT_BYTES * touched


def peak(device_kind: str, key: str) -> float:
    table = json.loads((Path(__file__).resolve().parents[1] / "peaks.json").read_text())
    return float(table["devices"][device_kind][key])


def read(run):
    if run.trace is None:
        return None
    device_s = run.trace["programs"].get("hetero_multi_step", 0.0)
    if device_s <= 0 or not run.phase_ops:
        return None
    least = sum(phase_bytes(run.kind, o, k, s) for o, k, s in run.phase_ops)
    return 100.0 * least / peak(run.device_kind, "hbm_bytes_per_s") / device_s
