"""The benchmark's harness: one run of one cell, from ``BENCHMARK.json``.

A cell names a configuration (``bench/configs/<config>.json``) and a traffic
mix (``bench/traffic/<traffic>.json``).  The configuration's ``system`` picks
the driver that runs the traffic through the program (``fabric`` or
``tier``); the per-layer metrics are read by ``bench/metrics/<metric>.py``.
Adding a cell or a metric adds files and edits none.

A run: set up (start JAX, load or build the durable state, warm every shape
up), measure for ``--seconds``, read the device's peak memory, then check what
the timed path produced against the plain reference (``bench/reference``)
and print the result as the last line of standard output.
"""

from __future__ import annotations

import argparse
import collections
import contextlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


class NoDevice(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def process_age_s() -> Optional[float]:
    """Seconds since this process started, from the kernel's record."""
    try:
        stat = Path("/proc/self/stat").read_text()
        start_ticks = int(stat.rsplit(")", 1)[1].split()[19])
        return time.clock_gettime(time.CLOCK_BOOTTIME) - start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return None


# ----------------------------------------------------------------- lookup
def load_spec(root: Path = ROOT) -> Dict[str, Any]:
    return json.loads((root / "BENCHMARK.json").read_text())


def load_cell(spec, name: str, data_dir: Path = BENCH_DIR):
    """(cell, config, traffic) of workload ``name``."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json: {sorted(cells)}")
    cell = cells[name]
    return (cell,) + load_parts(cell["config"], cell["traffic"], data_dir)


def load_parts(config: str, traffic: str, data_dir: Path = BENCH_DIR):
    """(config, traffic) documents of the files with these names."""
    return (json.loads((data_dir / "configs" / f"{config}.json").read_text()),
            json.loads((data_dir / "traffic" / f"{traffic}.json").read_text()))


def metric_reader(name: str) -> Callable:
    path = BENCH_DIR / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    module = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(module)
    return module.read


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) of all values, linearly interpolated."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def mount_of(path: Path) -> str:
    """File system type and mount point that hold ``path``."""
    best = ("?", "")
    try:
        for line in Path("/proc/mounts").read_text().splitlines():
            parts = line.split()
            if len(parts) > 2 and str(path).startswith(parts[1]) and len(parts[1]) > len(best[1]):
                best = (parts[2], parts[1])
    except OSError:
        pass
    return f"{best[0]} at {best[1]}"


# ---------------------------------------------------------------- runtime
class Spans:
    """The benchmark's host spans around its calls into the program: kept in
    memory by name, and written into the profiler trace when one is taken."""

    def __init__(self, traced: bool):
        self.durations: Dict[str, List[float]] = collections.defaultdict(list)
        self._annotation = None
        if traced:
            import jax

            self._annotation = jax.profiler.TraceAnnotation

    @contextlib.contextmanager
    def __call__(self, name: str):
        ann = self._annotation(name) if self._annotation else contextlib.nullcontext()
        with ann:
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.durations[name].append(time.perf_counter() - t0)

    def reset(self) -> None:
        self.durations.clear()


class CompileLog:
    """Programs compiled, or loaded from the persistent cache, as JAX's own
    monitoring events report them."""

    def __init__(self):
        import jax

        self.backend_compiles = 0
        self.names = collections.Counter()
        self.cache = collections.Counter()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.backend_compiles += 1
            self.names[kw.get("fun_name", "?")] += 1

    def _event(self, event, **kw):
        if event.startswith("/jax/compilation_cache/cache_"):
            self.cache[event.rsplit("/", 1)[1]] += 1

    def snapshot(self):
        return self.backend_compiles, sum(self.cache.values())

    def close(self) -> None:
        import jax

        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)


class Context:
    """What a driver gets from the harness."""

    def __init__(self, *, seed: int, traced: bool, store: Path, cache_dir: Path,
                 control: bool):
        self.seed = int(seed)
        self.spans = Spans(traced)
        self.store = store
        self.cache_dir = cache_dir
        self.control = control


def find_device(chips: int, require_tpu: bool):
    import jax

    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoDevice(f"no TPU: JAX's first device is {devices[0].platform!r}")
    if len(devices) < chips:
        raise NoDevice(f"the cell asks for {chips} chips, JAX has {len(devices)}")
    peaks = json.loads((BENCH_DIR / "peaks.json").read_text())["devices"]
    if require_tpu and devices[0].device_kind not in peaks:
        raise NoDevice(f"device {devices[0].device_kind!r} is not in bench/peaks.json")
    return devices[0], len(devices)


def drivers():
    from bench import fabric, tier

    return {"fabric": fabric.Driver, "tier": tier.Driver}


def run_cell(spec, name: str, *, seed: int, seconds: float, trace: bool,
             data_dir: Path = BENCH_DIR, require_tpu: bool = True,
             control: bool = False, t_start: Optional[float] = None,
             cache_dir: Path = BENCH_DIR / ".cache", log: Callable[[str], None] = lambda s: print(s, file=sys.stderr, flush=True)):
    """One run of cell ``name``; returns the result dict (the last line)."""
    t_start = time.perf_counter() if t_start is None else t_start
    cell, config, traffic = load_cell(spec, name, data_dir)
    dev, n_dev = find_device(int(cell["chips"]), require_tpu)
    import jax

    compiles = CompileLog()
    store = Path(tempfile.mkdtemp(prefix="dfc_bench_"))
    try:
        ctx = Context(seed=seed, traced=trace, store=store, cache_dir=cache_dir,
                      control=control)
        ctx.device_kind = dev.device_kind
        driver = drivers()[config["system"]](config, traffic, ctx, log)
        driver.setup()
        setup_s = time.perf_counter() - t_start
        log(f"setup: {setup_s:.3f} s; store on {mount_of(store)}")
        before = compiles.snapshot()
        compiles.names.clear()
        ctx.spans.reset()
        trace_dir = store / "trace"
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.host_tracer_level = 1
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        try:
            with ctx.spans("window"):
                window = driver.window(float(seconds))
        finally:
            if trace:
                jax.profiler.stop_trace()
        after = compiles.snapshot()
        log(f"window: {window['seconds']:.3f} s; backend compiles in window: "
            f"{after[0] - before[0]}; programs traced or loaded in window: "
            f"{after[1] - before[1]} {dict(compiles.names)}; generator lateness: "
            f"{driver.lateness()}")
        stats = dev.memory_stats() or {}
        device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": n_dev, "memory_peak_bytes": stats.get("peak_bytes_in_use")}
        trace_summary = None
        if trace:
            from bench import trace_reduce

            trace_summary = trace_reduce.reduce_trace(
                trace_reduce.find_trace(trace_dir), driver.span_names)
            device["busy_s"] = trace_summary["busy_s"]
            device["window_s"] = trace_summary["window_s"]
        checks = driver.check()
        result = {
            "correct": all(c["value"] <= c["limit"] for c in checks),
            "attempted": driver.attempted,
            "failed": driver.failed,
        }
        if trace:
            run = driver.record(trace_summary)
            metrics = {}
            for m in spec["per_layer"]:
                if name not in m.get("workloads", [name]):
                    continue
                value = metric_reader(m["name"])(run)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
            result["metrics"] = metrics
            result["device"] = device
            result["breakdown"] = {"device_ops": trace_summary["device_ops"],
                                   "idle_gaps": trace_summary["idle_gaps"]}
        else:
            e2e = driver.end_to_end()
            e2e["setup_s"] = setup_s
            metrics = {}
            for m in spec["end_to_end"]:
                if name in m.get("workloads", [name]):
                    metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
            result["metrics"] = metrics
            result["device"] = device
        result["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                            for c in checks}
        for c in checks:
            log(f"check {c['name']}: {c['value']} (limit {c['limit']})")
        return result
    finally:
        compiles.close()
        shutil.rmtree(store, ignore_errors=True)


def main(argv=None, t_start: Optional[float] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # the compile cache lives at a fixed path inside the checkout
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    try:
        from repro.launch.compile_cache import use_compile_cache

        use_compile_cache()
        result = run_cell(load_spec(), args.workload, seed=args.seed,
                          seconds=args.seconds, trace=bool(args.trace),
                          t_start=t_start)
    except NoDevice as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    print(json.dumps(result))
    return 0
