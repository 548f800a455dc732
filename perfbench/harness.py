"""The benchmark harness: finds a cell's configuration, traffic, path
driver and metric readers by the names in ``BENCHMARK.json``, runs the
cell once and prints the result line.

A cell is driven by ``paths/<path>.py`` (``<path>`` from the
configuration file), whose ``Cell(config, traffic, seed, trace, seconds)``
builds and warms everything in set-up, ``window(trace_dir)`` runs the
measured window and returns the run's record, and ``check(record)`` compares what
the window produced with the plain reference. Each metric is a reader
``metrics/<name>.py`` whose ``read(record)`` returns the number, or
``None`` when the run has nothing for it to read.
"""
from __future__ import annotations

import importlib.util
import json
import math
import statistics
import sys
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
TRACE_DIR = ROOT / "perfbench_out" / "trace"


def load_benchmark(path: Path = ROOT / "BENCHMARK.json") -> Dict:
    with open(path) as f:
        return json.load(f)


def cell_entries(bench: Dict, workload: str):
    """(workload entry, configuration entry) for the cell named
    ``workload``."""
    wl = {w["name"]: w for w in bench["workloads"]}.get(workload)
    if wl is None:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cfg = {c["name"]: c for c in bench["configs"]}[wl["config"]]
    return wl, cfg


def load_json(rel: str) -> Dict:
    with open(ROOT / rel) as f:
        return json.load(f)


def _load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def path_module(name: str):
    return _load_module(HERE / "paths" / f"{name}.py", f"perfbench_path_{name}")


def metric_reader(name: str) -> Callable[[Dict], Optional[float]]:
    return _load_module(HERE / "metrics" / f"{name}.py",
                        "perfbench_metric_" + name.replace(".", "_")).read


def cell_metrics(bench: Dict, workload: str, trace: bool) -> List[Dict]:
    """The metrics this cell reports: its end-to-end metrics untraced, its
    per-layer metrics traced."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group
            if "workloads" not in m or workload in m["workloads"]]


def percentile(values, q: float) -> float:
    """``q``-th percentile (0-100) by linear interpolation; an infinite
    value (a task never served) counts as a miss above every latency."""
    xs = sorted(values)
    if not xs:
        return float("nan")
    k = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    if xs[hi] == math.inf:
        return math.inf
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def median(values) -> float:
    return statistics.median(values) if len(values) else float("nan")


class CompileCounter:
    """Counts XLA backend compilations while ``active``: the measured
    window must compile nothing."""

    def __init__(self):
        import jax

        self.active = False
        self.count = 0
        self.seconds = 0.0

        def listener(event, duration, **kw):
            if self.active and "backend_compile" in event:
                self.count += 1
                self.seconds += duration

        jax.monitoring.register_event_duration_secs_listener(listener)


def enable_compile_cache() -> str:
    """The persistent compilation cache at the checkout's fixed path (or
    ``$JAX_COMPILATION_CACHE_DIR``), with every program cached, small ones
    too, so that a cell's second run compiles nothing."""
    import jax

    sys.path[:0] = [p for p in (str(ROOT / "src"),) if p not in sys.path]
    from repro.launch.compile_cache import enable_compile_cache as enable

    where = enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def stage(name: str, t0: float) -> float:
    """Log the seconds a set-up stage took since ``t0``; return the clock."""
    now = time.perf_counter()
    log(f"setup stage {name}: {now - t0!r} s")
    return now


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             t_process: float, bench: Optional[Dict] = None,
             require_tpu: bool = True, config_override: Optional[Dict] = None,
             traffic_override: Optional[Dict] = None) -> Optional[Dict]:
    """Run one cell once and return the result object (``None``, after a
    message on standard error, when the device does not fit the cell).
    ``require_tpu=False`` and the overrides are for the CPU tests, which
    drive the rest of a run at a size a test can hold."""
    import jax

    from perfbench import traffic as traffic_mod
    from perfbench import work

    t = stage("imports and compile cache", t_process)
    bench = bench or load_benchmark()
    wl, cfg_entry = cell_entries(bench, workload)
    devs = jax.devices()
    t = stage("device", t)
    dev = devs[0]
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devs)}")
    if require_tpu and (dev.platform != "tpu" or len(devs) < wl["chips"]):
        log(f"perfbench: cell {workload!r} needs {wl['chips']} TPU chip(s); "
            f"JAX found {len(devs)} {dev.platform} device(s). There is no "
            "CPU fallback.")
        return None
    pk = work.peaks(dev.device_kind) if require_tpu else None
    config = config_override or load_json(cfg_entry["file"])
    mix = traffic_override or traffic_mod.load(wl["traffic"])
    mod = path_module(config["path"])
    counter = CompileCounter()
    t = stage("path module", t)
    cell = mod.Cell(config, mix, seed, trace=trace, seconds=seconds)
    setup_s = time.perf_counter() - t_process
    log(f"setup_s={setup_s!r}")
    counter.active = True
    rec = cell.window(str(TRACE_DIR / f"{workload}-{seed}")
                      if trace else None)
    counter.active = False
    log(f"compilations inside the window: {counter.count} "
        f"({counter.seconds!r} s)")
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    rec.update(setup_s=setup_s, peaks=pk, config=config, traffic=mix,
               compiles_in_window=counter.count)
    checks = cell.check(rec)
    correct = bool(rec["failed"] == 0 and all(
        c["value"] <= c["limit"] for c in checks.values()))
    metrics = {}
    for m in cell_metrics(bench, workload, trace):
        value = metric_reader(m["name"])(rec)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": wl["chips"], "memory_peak_bytes": peak}
    out = {"correct": correct, "attempted": rec["attempted"],
           "failed": rec["failed"], "metrics": metrics, "device": device}
    if trace:
        red = rec["trace"]
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        out["breakdown"] = red["breakdown"]
    out["checks"] = checks
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    return out
