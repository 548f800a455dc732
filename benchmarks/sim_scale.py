"""Internet-scale simulation benchmark (DESIGN.md §11).

Measures the array-based :class:`~repro.sim.EventCalendar` driver loop
against the scalar :class:`~repro.sim.EventHeap` oracle it replaced, and
pins the determinism contracts the refactor must keep:

- **byte_identity** — a nontrivial real-engine scenario (PAPER_NODES
  fleet, mixed open-loop Poisson arrivals + closed-loop tenant
  populations with SLO/retry/backoff, intensity ticks) renders a
  byte-identical ``metrics.to_text()`` across *all four* combinations of
  ``event_queue`` x ``batch_execute``.
- **replay** — per-event cost of the event machinery itself, measured
  with a constant-cost null executor so the engine's scoring/execute
  work (unchanged by this PR) doesn't mask the loop being measured: a
  precomputed arrival schedule replayed through heap vs calendar, wall
  clock, per-event microseconds, speedup and peak RSS. This is the
  headline >=10x surface: with every event staged before the first pop
  the calendar drains long same-kind array runs.
- **closed_loop** — the same measurement on a closed-loop tenant
  scenario (think/SLO/retry/backoff). Each batch drain re-arms at most
  one window-flush timer, so the oracle semantics themselves fragment
  runs to the inter-flush spacing and the speedup is structurally
  smaller; the gate asserts byte identity plus a loose floor here.
  Heap-vs-calendar byte identity is asserted on every row of both
  sections as a free side effect.
- **trace_replay** — a day-long multi-region ElectricityMaps-style CSV
  is synthesized, ingested via :meth:`TraceProvider.from_csv`, and a
  24 h sim over it must be byte-deterministic across a repeat run, both
  event queues and both execute paths.

Smoke mode (the ``sim_scale`` CI gate) sizes the rows at ~2*10^4 and
~10^5 processed events; the full sweep (``--full``) adds the acceptance
rows — a heap-vs-calendar byte-identity replay at 10^7 events, then
10^6 closed-loop clients (~10^7 events) through the calendar.

    PYTHONPATH=src:. python -m benchmarks.sim_scale [--full]
"""
from __future__ import annotations

import gc
import json
import resource
from contextlib import contextmanager
from time import perf_counter

import numpy as np

from repro.core.api import CarbonEdgeEngine
from repro.core.cluster import EdgeCluster, PAPER_NODES
from repro.core.providers import TraceProvider
from repro.core.scheduler import Task
from repro.sim import (AsyncEngineDriver, ClientPopulation,
                       ClosedLoopClientPool, PoissonArrivals,
                       TraceReplayArrivals)

SEED = 20260808
TASK = Task(cpu=0.05, mem_mb=16.0, base_latency_ms=250.0)
CSV_ZONES = ("DE", "FR", "PL")


# ---------------------------------------------------------------------------
# Null executor: constant-cost step, so the rows measure the event loop
# ---------------------------------------------------------------------------


class _NullResult:
    """Shared constant result; the driver only reads these attributes.
    Service time is small enough that the benchmark fleet stays
    unsaturated — the regime where long same-kind runs exist for the
    calendar to batch (the saturated regime degrades both queues to
    one event per batch and is covered by the tests, not timed here)."""
    __slots__ = ()
    latency_ms = 0.05
    energy_kwh = 1e-6
    carbon_g = 0.5
    node = "n0"


class NullExecutor:
    """O(1)-per-step executor with fixed per-task cost.

    Isolates the quantity this benchmark gates — driver/event-queue
    overhead per event — from the engine's scoring and billing work,
    which dominates wall clock in a real scenario and is unchanged by
    the calendar refactor. Exposes the same surface the driver uses on
    a real engine: ``submit``/``submit_many``/``step`` plus the
    ``last_exec`` column snapshot, with the snapshot carrying exactly
    the floats the result objects do (so heap and calendar runs stay
    byte-identical).
    """

    def __init__(self, max_batch: int):
        self._queued = 0
        self._res = _NullResult()
        self._uniq = np.array([_NullResult.node])
        self._inv = np.zeros(max_batch, dtype=np.int64)
        self._lat = np.full(max_batch, _NullResult.latency_ms)
        self._ekwh = np.full(max_batch, _NullResult.energy_kwh)
        self._cg = np.full(max_batch, _NullResult.carbon_g)
        self.last_exec = None

    def submit(self, task) -> None:
        self._queued += 1

    def submit_many(self, tasks) -> None:
        self._queued += len(tasks)

    def step(self, now_hour: float = 0.0, limit=None):
        k = self._queued if limit is None else min(self._queued, limit)
        self._queued -= k
        self.last_exec = (self._uniq, self._inv[:k], self._lat[:k],
                          self._ekwh[:k], self._cg[:k])
        return [self._res] * k


def _null_driver(n_clients: int, horizon_hours: float,
                 event_queue: str, max_batch: int = 256) -> AsyncEngineDriver:
    """Closed-loop scenario against the null executor: a bulk tenant that
    always meets its SLO and a strict tenant that never does (its SLO is
    below the constant service time), so the run exercises first tries,
    retries, backoff and abandonment deterministically."""
    n_bulk = (n_clients * 4) // 5
    pool = ClosedLoopClientPool([
        ClientPopulation("bulk", n_bulk, mean_think_hours=0.02),
        ClientPopulation("strict", n_clients - n_bulk,
                         mean_think_hours=0.03, slo_latency_s=1e-5,
                         max_attempts=3, priority=1),
    ], seed=SEED)
    return AsyncEngineDriver(
        NullExecutor(max_batch), None, lambda uid, hour, tenant: uid,
        horizon_hours=horizon_hours, max_batch=max_batch,
        batch_window_hours=5e-4, clients=pool, event_queue=event_queue)


def _peak_rss_mb() -> float:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0


@contextmanager
def _nogc():
    """Cyclic GC off around a timed run (both queues get the identical
    treatment). At 10^7 staged events every gen2 collection walks the
    whole live population, which turns the *heap* run superlinear —
    refcounting still frees popped events, so disabling the collector
    only removes scan time. The heap benefits far more than the
    calendar (whose events are rows in a handful of arrays), so the
    reported speedups are conservative."""
    was = gc.isenabled()
    gc.collect()
    gc.disable()
    try:
        yield
    finally:
        if was:
            gc.enable()


def _replay_driver(arrival_hours: np.ndarray, horizon_hours: float,
                   event_queue: str,
                   max_batch: int = 1024) -> AsyncEngineDriver:
    """Open-loop replay of a precomputed arrival schedule — the pure
    array-drain case (every event is staged before the first pop), so a
    large ``max_batch`` lets the calendar amortize its fixed per-batch
    numpy cost over long same-kind runs."""
    return AsyncEngineDriver(
        NullExecutor(max_batch), TraceReplayArrivals(arrival_hours),
        lambda uid, hour: uid, horizon_hours=horizon_hours,
        max_batch=max_batch, batch_window_hours=5e-4,
        event_queue=event_queue)


def bench_replay(n_arrivals: int, heap_oracle: bool = True) -> dict:
    """One replay row: the same recorded schedule through both queues."""
    rng = np.random.default_rng(SEED + 2)
    horizon = n_arrivals / 600_000.0          # ~600k arrivals per sim-hour
    ts = np.sort(rng.uniform(0.0, horizon, n_arrivals))
    runs = {}
    for q in (("calendar", "heap") if heap_oracle else ("calendar",)):
        drv = _replay_driver(ts, horizon, q)
        with _nogc():
            t0 = perf_counter()
            m = drv.run()
            wall = perf_counter() - t0
        runs[q] = {"wall_s": wall, "events": drv.events_processed,
                   "text": m.to_text() if heap_oracle else None,
                   "tasks": m.n_records}
    cal = runs["calendar"]
    assert cal["tasks"] == n_arrivals, (cal["tasks"], n_arrivals)
    row = {
        "n_arrivals": n_arrivals,
        "events": cal["events"],
        "calendar_wall_s": round(cal["wall_s"], 4),
        "calendar_per_event_us": round(cal["wall_s"] / cal["events"] * 1e6,
                                       4),
        "peak_rss_mb": round(_peak_rss_mb(), 1),
    }
    if heap_oracle:
        heap = runs["heap"]
        assert heap["events"] == cal["events"]
        row["heap_wall_s"] = round(heap["wall_s"], 4)
        row["heap_per_event_us"] = round(
            heap["wall_s"] / heap["events"] * 1e6, 4)
        row["speedup_x"] = round(row["heap_per_event_us"]
                                 / row["calendar_per_event_us"], 2)
        row["byte_identity"] = heap["text"] == cal["text"]
    return row


def bench_row(n_clients: int, horizon_hours: float,
              heap_oracle: bool = True) -> dict:
    """One speedup row: same scenario through both queues (heap skipped
    at full scale, where the scalar loop would take minutes)."""
    runs = {}
    for q in (("calendar", "heap") if heap_oracle else ("calendar",)):
        drv = _null_driver(n_clients, horizon_hours, q)
        with _nogc():
            t0 = perf_counter()
            m = drv.run()
            wall = perf_counter() - t0
        runs[q] = {"wall_s": wall, "events": drv.events_processed,
                   "tasks": m.n_records,
                   "text": m.to_text() if heap_oracle else None,
                   "summary": m.summary()}
    cal = runs["calendar"]
    row = {
        "n_clients": n_clients,
        "horizon_hours": horizon_hours,
        "events": cal["events"],
        "tasks": cal["tasks"],
        "calendar_wall_s": round(cal["wall_s"], 4),
        "calendar_per_event_us": round(cal["wall_s"] / cal["events"] * 1e6,
                                       4),
        "peak_rss_mb": round(_peak_rss_mb(), 1),
    }
    if heap_oracle:
        heap = runs["heap"]
        assert heap["events"] == cal["events"], (heap["events"],
                                                 cal["events"])
        row["heap_wall_s"] = round(heap["wall_s"], 4)
        row["heap_per_event_us"] = round(
            heap["wall_s"] / heap["events"] * 1e6, 4)
        row["speedup_x"] = round(row["heap_per_event_us"]
                                 / row["calendar_per_event_us"], 2)
        row["byte_identity"] = heap["text"] == cal["text"]
    return row


# ---------------------------------------------------------------------------
# Real-engine byte identity: event_queue x batch_execute
# ---------------------------------------------------------------------------


def _engine_driver(event_queue: str, batch_execute: bool,
                   provider=None, horizon_hours: float = 0.12,
                   tick_hours: float = 0.05) -> AsyncEngineDriver:
    cluster = EdgeCluster(nodes=PAPER_NODES, host_power_w=142.0)
    cluster.profile(250.0)
    engine = CarbonEdgeEngine(cluster, mode="green", provider=provider,
                              batch_execute=batch_execute)
    pool = ClosedLoopClientPool([
        ClientPopulation("interactive", 180, mean_think_hours=0.01,
                         slo_latency_s=2.0, max_attempts=3, priority=1),
        ClientPopulation("batch", 120, mean_think_hours=0.02),
    ], seed=SEED + 1)
    return AsyncEngineDriver(
        engine, PoissonArrivals(200.0, seed=3),
        lambda uid, hour, tenant: TASK,
        horizon_hours=horizon_hours, max_batch=16,
        batch_window_hours=0.002, tick_hours=tick_hours, clients=pool,
        slo_latency_s=2.0, event_queue=event_queue)


def engine_identity() -> dict:
    """All four event_queue x batch_execute combinations must render the
    same metrics text byte for byte (the heap-oracle contract)."""
    texts = {}
    for q in ("heap", "calendar"):
        for be in (True, False):
            m = _engine_driver(q, be).run()
            texts[f"{q}_batchexec_{be}"] = m.to_text()
    ref_key, ref = "heap_batchexec_True", texts["heap_batchexec_True"]
    return {key: (texts[key] == ref) for key in texts if key != ref_key}


# ---------------------------------------------------------------------------
# Multi-region CSV trace replay
# ---------------------------------------------------------------------------


def synth_csv(n_hours: int = 24) -> str:
    """A day-long ElectricityMaps-style export: one row per
    (timestamp, zone), deterministic diurnal shapes per zone."""
    bases = {"DE": 320.0, "FR": 60.0, "PL": 710.0}
    amps = {"DE": 120.0, "FR": 15.0, "PL": 90.0}
    lines = ["datetime,zone_name,carbon_intensity_avg"]
    for h in range(n_hours):
        for z in CSV_ZONES:
            v = bases[z] - amps[z] * np.sin((h - 6.0) / 24.0 * 2 * np.pi)
            lines.append(f"2026-08-07T{h:02d}:00:00Z,{z},{v:.3f}")
    return "\n".join(lines) + "\n"


def trace_replay() -> dict:
    """24 h sim over the ingested CSV: deterministic across a repeat run,
    both event queues and both execute paths."""
    csv_text = synth_csv()
    node_zones = {n.name: CSV_ZONES[i % len(CSV_ZONES)]
                  for i, n in enumerate(PAPER_NODES)}

    def one(event_queue: str, batch_execute: bool) -> str:
        provider = TraceProvider.from_csv(csv_text, node_zones=node_zones)
        cluster = EdgeCluster(nodes=PAPER_NODES, host_power_w=142.0)
        cluster.profile(250.0)
        engine = CarbonEdgeEngine(cluster, mode="green", provider=provider,
                                  batch_execute=batch_execute)
        drv = AsyncEngineDriver(
            engine, PoissonArrivals(40.0, seed=7),
            lambda uid, hour: TASK, horizon_hours=24.0, max_batch=16,
            batch_window_hours=0.01, tick_hours=1.0,
            event_queue=event_queue)
        return drv.run().to_text()

    ref = one("calendar", True)
    return {
        "zones": len(CSV_ZONES),
        "trace_hours": 24,
        "repeat_match": one("calendar", True) == ref,
        "queue_match": one("heap", True) == ref,
        "exec_path_match": one("calendar", False) == ref,
    }


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------


def run(smoke: bool = True, out_path: str = "BENCH_sim_scale.json") -> dict:
    _null_driver(64, 0.02, "calendar").run()      # warm numpy dispatch
    _null_driver(64, 0.02, "heap").run()
    replay = [
        bench_replay(20_000),                     # ~2*10^4 events
        bench_replay(120_000),                    # >=10^5 events
    ]
    closed_loop = [
        bench_row(2_000, 0.25),                   # ~3*10^4 events
        bench_row(10_000, 0.4),                   # ~2.5*10^5 events
    ]
    out = {
        "byte_identity": engine_identity(),
        "replay": replay,
        "closed_loop": closed_loop,
        "trace_replay": trace_replay(),
    }
    if not smoke:
        # acceptance scale: a heap-vs-calendar byte-identity replay at
        # 10^7 events, then 10^6 closed-loop clients through the
        # calendar alone (the scalar oracle at this scale is the point
        # of the refactor).
        print("full: 10^7-event replay (heap oracle)...", flush=True)
        out["replay_identity_1e7"] = bench_replay(10_000_000)
        print("full: 10^6 closed-loop clients (calendar)...", flush=True)
        out["full_scale"] = bench_row(1_000_000, 0.2, heap_oracle=False)
    with open(out_path, "w") as f:
        json.dump(out, f, indent=2)
    print(f"wrote {out_path}")
    return out


if __name__ == "__main__":
    import argparse

    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--full", action="store_true",
                   help="add the 10^6-client / 10^7-event acceptance rows")
    args = p.parse_args()
    out = run(smoke=not args.full)
    print(json.dumps({k: v for k, v in out.items()
                      if k != "byte_identity"} | {
                          "byte_identity": out["byte_identity"]},
                     indent=2))
