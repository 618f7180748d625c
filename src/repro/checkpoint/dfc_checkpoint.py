"""DFC-Checkpoint: the paper's detectable flat-combining persistence protocol
as a distributed checkpoint manager.

This is the hardware adaptation of DFC's core insight (DESIGN.md §4): at
datacenter scale the expensive persistence instruction is the device→host
fetch + durable file write (`pwb` analogue) and the fsync barrier (`pfence`
analogue).  DFC's structure transfers verbatim:

  tAnn  -> per-worker double-buffered announcement records (announce/ann{0,1}
           + a `valid` selector), written and fsynced by workers in parallel
  cEpoch-> an epoch file committed with the TWO-INCREMENT protocol: persist
           v+1, publish v+2 without persisting — recovery rounds odd -> even
  top[2]-> two alternating checkpoint slots; a combining phase writes ONLY
           the inactive slot; the epoch parity selects the active one
  Reduce-> elimination: K workers' announcements are combined into ONE slot
           persist (the newest state subsumes all K requests) — persistence
           cost per announcement drops as 1/K, the paper's Figure-3 effect
  GC    -> recovery rebuilds the slot-file index from the active manifest and
           deletes unreachable tensor files (volatile bitmap analogue)

Detectability: Recover() reports, for every worker, whether its announced
step committed and at which epoch — training resumes exactly-once (no step
replayed into the optimizer twice, none lost).

Durability is simulated through SimFS: writes are buffered in memory and hit
the real filesystem only at fsync; a crash drops unsynced buffers (or flushes
an adversarial subset), exactly like the NVM cache model in repro.nvm.
"""

from __future__ import annotations

import dataclasses
import json
import os
import shutil
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

import numpy as np

import jax

from repro.nvm.memory import DEFAULT_TAG, PersistStats
from repro.obs import NULL_OBS, count, recording, span


class CrashNow(Exception):
    """Raised by FaultInjector at the scheduled persistence op."""


@dataclasses.dataclass
class FaultInjector:
    """Crash at the k-th persistence operation (pwb or pfence)."""

    crash_at: Optional[int] = None
    count: int = 0

    def tick(self):
        self.count += 1
        if self.crash_at is not None and self.count >= self.crash_at:
            raise CrashNow(f"injected crash at persistence op {self.count}")


class SimFS:
    """Buffered filesystem: content reaches disk only at fsync (pwb=write,
    pfence=fsync).  Crash drops unsynced buffers.

    Persistence ops carry an optional attribution ``tag`` (announce, slot,
    resp, epoch, routing, ...) counted into ``pstats`` — a
    :class:`PersistStats` partitioning the legacy ``stats`` totals by
    protocol step.  An observer (``repro.obs.FabricObserver``) may be
    attached via ``obs``; its hooks run strictly AFTER the counters, the
    fault-injector tick, and the durable work, so tracing can never perturb
    counts, crash points, or on-disk bytes.
    """

    def __init__(self, root: Path, injector: Optional[FaultInjector] = None):
        self.root = Path(root)
        self.root.mkdir(parents=True, exist_ok=True)
        self.pending: Dict[str, bytes] = {}
        self.injector = injector or FaultInjector()
        self.stats = {"pwb": 0, "pfence": 0}
        self.pstats = PersistStats()
        self.obs = NULL_OBS

    def _p(self, rel: str) -> Path:
        return self.root / rel

    def write(self, rel: str, data: bytes, tag: Optional[str] = None) -> None:
        """pwb: buffered write — NOT durable until fsync."""
        self.stats["pwb"] += 1
        self.pstats.count_pwb(tag)
        self.injector.tick()
        self.pending[rel] = data
        self.obs.on_pwb(rel, tag)

    def fsync(self, rels: Optional[List[str]] = None, tag: Optional[str] = None) -> None:
        """pfence: flush pending writes to the real filesystem (program span
        ``pfence.<tag>``)."""
        with span("pfence", tag or DEFAULT_TAG):
            self.stats["pfence"] += 1
            self.pstats.count_pfence(tag)
            self.injector.tick()
            items = (
                list(self.pending.items())
                if rels is None
                else [(r, self.pending[r]) for r in rels if r in self.pending]
            )
            for rel, data in items:
                p = self._p(rel)
                p.parent.mkdir(parents=True, exist_ok=True)
                p.write_bytes(data)
                self.pending.pop(rel, None)
        self.obs.on_pfence(rels, tag)

    def read(self, rel: str) -> Optional[bytes]:
        """Reads see the buffered (volatile) view, like a CPU cache."""
        if rel in self.pending:
            return self.pending[rel]
        return self.read_durable(rel)

    def read_durable(self, rel: str) -> Optional[bytes]:
        """Read from the file system (counters ``disk_reads`` and
        ``disk_read_bytes``)."""
        p = self._p(rel)
        if not p.exists():
            return None
        data = p.read_bytes()
        if recording():
            count("disk_reads")
            count("disk_read_bytes", len(data))
        return data

    def exists(self, rel: str) -> bool:
        return rel in self.pending or self._p(rel).exists()

    def listdir(self, rel: str) -> List[str]:
        p = self._p(rel)
        disk = [f"{rel}/{x}" for x in os.listdir(p)] if p.exists() else []
        buf = [k for k in self.pending if k.startswith(rel + "/")]
        return sorted(set(disk) | set(buf))

    def delete(self, rel: str) -> None:
        self.pending.pop(rel, None)
        p = self._p(rel)
        if p.exists():
            p.unlink()

    def crash(self) -> "SimFS":
        """Lose all unsynced writes; return a fresh post-crash view."""
        fs = SimFS(self.root, FaultInjector())
        return fs


BOT = None


class DFCCheckpointManager:
    """Detectable flat-combining checkpoint manager (one per job).

    Workers call ``announce(worker, payload)``; the coordinator calls
    ``combine(state)`` which persists one combined checkpoint for every ready
    announcement and publishes it with the two-increment epoch commit.
    ``recover()`` fixes the epoch, garbage-collects the slot pool, re-commits
    pending announcements (using the caller-provided state getter), and
    returns each worker's detectability verdict.
    """

    def __init__(self, fs: SimFS, n_workers: int, prefix: str = ""):
        """``prefix`` roots every durable path of this manager under a
        subdirectory of ``fs`` — multiple managers (e.g. a sharded fabric
        plus its reshard donor-snapshot log) can then share ONE SimFS, so
        fault injection sweeps tick through every manager's persistence ops.
        """
        self.fs = fs
        self.n = n_workers
        self.prefix = prefix if (not prefix or prefix.endswith("/")) else prefix + "/"

    def _rel(self, rel: str) -> str:
        return self.prefix + rel

    # ------------------------------------------------------------- epoch I/O
    def _read_epoch(self) -> int:
        raw = self.fs.read(self._rel("cEpoch"))
        return int(raw.decode()) if raw else 0

    def _write_epoch(self, v: int, sync: bool) -> None:
        self.fs.write(self._rel("cEpoch"), str(v).encode())
        if sync:
            self.fs.fsync([self._rel("cEpoch")])

    # ---------------------------------------------------------- announcements
    def _ann_path(self, w: int, slot: int) -> str:
        return self._rel(f"tAnn/worker_{w}/ann{slot}.json")

    def _valid_path(self, w: int) -> str:
        return self._rel(f"tAnn/worker_{w}/valid")

    def _read_valid(self, w: int) -> int:
        raw = self.fs.read(self._valid_path(w))
        return int(raw.decode()) if raw else 0

    def _read_ann(self, w: int, slot: int) -> Dict[str, Any]:
        raw = self.fs.read(self._ann_path(w, slot))
        return json.loads(raw.decode()) if raw else {"val": BOT, "epoch": -1}

    def announce(self, worker: int, payload: Dict[str, Any]) -> None:
        """Worker-side announcement (paper lines 2-12), parallel pwb/pfence."""
        epoch = self._read_epoch()
        if epoch % 2 == 1:
            epoch += 1
        valid = self._read_valid(worker)
        n_op = 1 - (valid & 1)
        ann = dict(payload, val=BOT, epoch=epoch)
        self.fs.write(self._ann_path(worker, n_op), json.dumps(ann).encode())
        self.fs.fsync([self._ann_path(worker, n_op)])  # L9
        self.fs.write(self._valid_path(worker), str(n_op).encode())
        self.fs.fsync([self._valid_path(worker)])  # L11
        self.fs.write(self._valid_path(worker), str(2 | n_op).encode())  # L12 MSB

    def ready_announcements(self) -> List[int]:
        out = []
        for w in range(self.n):
            v = self._read_valid(w)
            if (v >> 1) & 1:
                ann = self._read_ann(w, v & 1)
                if ann.get("val") is BOT and ann.get("step") is not None:
                    out.append(w)
        return out

    # ---------------------------------------------------------------- combine
    def _slot_dir(self, epoch: int, nxt: bool) -> str:
        idx = (epoch // 2 + (1 if nxt else 0)) % 2
        return self._rel(f"top/slot{idx}")

    def combine(self, state_tree, extra_meta: Optional[Dict] = None) -> List[int]:
        """One combining phase: persist `state_tree` into the inactive slot
        for ALL ready announcements (elimination: K requests -> 1 persist),
        set responses, two-increment commit.  Returns combined workers."""
        epoch = self._read_epoch()
        assert epoch % 2 == 0, "combine under an uncommitted epoch"
        ready = self.ready_announcements()
        if not ready:
            return []

        slot = self._slot_dir(epoch, nxt=True)
        leaves, treedef = jax.tree_util.tree_flatten(state_tree)
        manifest = {"leaves": [], "epoch": epoch + 2, "meta": extra_meta or {}}
        files = []
        for i, leaf in enumerate(leaves):
            arr = np.asarray(leaf)
            rel = f"{slot}/leaf_{i}.npy"
            import io

            buf = io.BytesIO()
            np.save(buf, arr)
            self.fs.write(rel, buf.getvalue())  # pwb per tensor
            files.append(rel)
            manifest["leaves"].append({"file": f"leaf_{i}.npy", "shape": list(arr.shape), "dtype": str(arr.dtype)})
        self.fs.write(f"{slot}/manifest.json", json.dumps(manifest).encode())
        files.append(f"{slot}/manifest.json")

        # responses into the combined announcements (paper L92/L61: epoch+val)
        for w in ready:
            v = self._read_valid(w)
            ann = self._read_ann(w, v & 1)
            ann["epoch"] = epoch
            ann["val"] = "ACK"
            self.fs.write(self._ann_path(w, v & 1), json.dumps(ann).encode())
            files.append(self._ann_path(w, v & 1))

        # single pfence for slot + responses (paper L80)
        self.fs.fsync(files)
        # two-increment epoch commit (paper L81-83)
        self._write_epoch(epoch + 1, sync=True)
        self._write_epoch(epoch + 2, sync=False)
        return ready

    # ---------------------------------------------------------------- recover
    def recover(self, state_getter: Optional[Callable[[], Any]] = None):
        """Recovery combiner (paper lines 26-43) + detectability report.

        Returns (restored_state_leaves_or_None, report) where report[w] =
        {"committed": bool, "step": int|None} for each worker's latest
        announcement."""
        fs = self.fs
        epoch = self._read_epoch()
        if epoch % 2 == 1:  # L28-30
            epoch += 1
            self._write_epoch(epoch, sync=True)

        # garbage-collect the slot pool (paper §4): keep only files reachable
        # from the ACTIVE slot's manifest
        active = self._slot_dir(epoch, nxt=False)
        inactive = self._slot_dir(epoch, nxt=True)
        man_raw = fs.read_durable(f"{active}/manifest.json")
        live = set()
        if man_raw:
            man = json.loads(man_raw.decode())
            live = {f"{active}/{e['file']}" for e in man["leaves"]}
            live.add(f"{active}/manifest.json")
        for rel in list(fs.listdir(active)) + list(fs.listdir(inactive)):
            if rel not in live:
                fs.delete(rel)

        # announcements scan (L32-38)
        pending = []
        for w in range(self.n):
            v = self._read_valid(w)
            lsb = v & 1
            if (v >> 1) & 1 == 0:
                fs.write(self._valid_path(w), str(2 | lsb).encode())  # L36
            ann = self._read_ann(w, lsb)
            if ann.get("epoch") == epoch and ann.get("val") is not BOT:
                ann["val"] = BOT  # L38: re-commit ops of the crashed phase
                fs.write(self._ann_path(w, lsb), json.dumps(ann).encode())
            if ann.get("val") is BOT and ann.get("step") is not None:
                pending.append(w)

        # restore the active state
        state = None
        if man_raw:
            man = json.loads(man_raw.decode())
            state = [
                np.load(io_bytes(fs.read_durable(f"{active}/{e['file']}")))
                for e in man["leaves"]
            ]

        # recovery combine (L39).  Divergence from the stack (documented in
        # DESIGN.md §4): a stack announcement is self-contained, so the paper
        # re-executes it; a checkpoint announcement's payload (device state)
        # died with the crash.  If the runtime can still produce the state
        # (coordinator-only failure), roll FORWARD by re-combining; otherwise
        # write the definite negative verdict LOST — the worker re-runs from
        # the committed slot (exactly-once at the training-step level).
        if pending:
            if state_getter is not None:
                self.combine(state_getter())
            else:
                files = []
                for w in pending:
                    v = self._read_valid(w)
                    ann = self._read_ann(w, v & 1)
                    ann["val"] = "LOST"
                    fs.write(self._ann_path(w, v & 1), json.dumps(ann).encode())
                    files.append(self._ann_path(w, v & 1))
                fs.fsync(files)

        report = {}
        for w in range(self.n):
            v = self._read_valid(w)
            ann = self._read_ann(w, v & 1)
            report[w] = {
                "committed": ann.get("val") == "ACK" and ann.get("step") is not None,
                "step": ann.get("step"),
            }
        return state, report

    def load_active(self):
        """Read the committed checkpoint (leaves list + manifest meta)."""
        epoch = self._read_epoch()
        if epoch % 2 == 1:
            epoch += 1
        active = self._slot_dir(epoch, nxt=False)
        man_raw = self.fs.read_durable(f"{active}/manifest.json")
        if not man_raw:
            return None, None
        man = json.loads(man_raw.decode())
        leaves = [
            np.load(io_bytes(self.fs.read_durable(f"{active}/{e['file']}")))
            for e in man["leaves"]
        ]
        return leaves, man

    # ------------------------------------------------- DFC structure states
    # The manager's combine() persists any pytree; these wrappers add the
    # structure-aware layer for the array-backed DFC states of
    # ``repro.core.jax_dfc``: the buffer is persisted ALONGSIDE its
    # double-buffered root counters — ``size[2]`` for the stack,
    # ``ends[2, 2]`` = (head, tail) / (left, right) for the ring-backed queue
    # and deque — under the same two-increment epoch commit, and the manifest
    # records the kind so ``load_structure`` can rebuild the typed state.
    def combine_structure(self, state, extra_meta: Optional[Dict] = None) -> List[int]:
        """Persist a StackState / QueueState / DequeState / MapState for every
        ready announcement (same elimination + two-increment commit as
        combine)."""
        from repro.core.jax_dfc import struct_kind

        kind = struct_kind(state)
        meta = dict(extra_meta or {})
        meta["struct"] = kind
        meta["struct_epoch"] = int(state.epoch)
        if kind == "stack":
            meta["committed_size"] = int(state.active_size())
        elif kind == "map":
            meta["committed_count"] = int(state.active_count())
        else:
            ends = state.active_ends()
            meta["committed_ends"] = [int(ends[0]), int(ends[1])]
        return self.combine(state, extra_meta=meta)

    def load_structure(self):
        """Rebuild the committed structure state (typed) from the active
        slot.  Returns (state, manifest) or (None, None)."""
        from repro.core.jax_dfc import STRUCTS

        import jax.numpy as jnp

        leaves, man = self.load_active()
        if leaves is None:
            return None, None
        kind = man["meta"].get("struct")
        if kind is None:
            raise ValueError("active checkpoint was not written by combine_structure")
        fresh = STRUCTS[kind].init(1)
        treedef = jax.tree_util.tree_structure(fresh)
        return (
            jax.tree_util.tree_unflatten(
                treedef, [jnp.asarray(leaf) for leaf in leaves]
            ),
            man,
        )


def io_bytes(data: bytes):
    import io

    return io.BytesIO(data)
