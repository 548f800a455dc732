"""Resilience under churn: fault injection sweep + zero-fault overhead.

Three claims are measured (DESIGN.md §10) and asserted by
``gate_resilience``:

- **zero-fault bit-identity**: an engine with resilience attached but an
  empty fault schedule renders a byte-identical sim ``to_text`` to a
  resilience-free engine, on both execute paths; and a fixed fault seed
  reproduces a faulted run byte-identically;
- **overhead**: with resilience attached and no faults, the end-to-end
  ``engine.step`` stays within 1.1x of a bare engine at N=10^4, B=1024
  (interleaved timing, median of adjacent-pair ratios);
- **degraded-mode quality**: sweeping node churn rate x provider outage
  rate, the framework keeps serving — reporting request availability
  (completed / submitted), SLO violation rate, dead-letter counts, the
  schedule's MTTR, and the carbon regret of lagged failure detection
  against the fault-oracle run (same faults, zero detection lag — the
  scheduler that never places onto a dead node).

Writes ``BENCH_resilience.json``. The CI smoke runs ``run(smoke=True)``;
gate assertions live in ``benchmarks/ci_gates.py``
(``python -m benchmarks.ci_gates resilience``).
"""
from __future__ import annotations

import json
import time
from typing import Dict

from benchmarks.fleet_scale import make_fleet, make_tasks

OVERHEAD_ROW = (10_000, 1024)
OVERHEAD_BOUND_X = 1.1
AVAILABILITY_FLOOR = 0.95

# (crash_rate_per_hour, outage_rate_per_hour) sweep cells; the first is
# the baseline-churn cell the availability gate asserts on.
FULL_CELLS = ((1.0, 0.0), (1.0, 1.0), (3.0, 1.0), (8.0, 2.0))
SMOKE_CELLS = ((1.0, 0.0), (8.0, 2.0))


def _sim(faults=None, *, resilient: bool = True, n_nodes: int = 6,
         horizon: float = 0.5, seed: int = 11,
         batch_execute: bool = True):
    """One churn sim: Poisson arrivals over a small heterogeneous fleet
    with out-of-phase diurnal intensity traces (time-varying, so delayed
    or re-placed work has a real carbon cost), resilience + the
    last-known-good provider wired, faults optional."""
    import numpy as np

    from repro.core.api import CarbonEdgeEngine
    from repro.core.cluster import EdgeCluster, NodeSpec
    from repro.core.providers import TraceProvider
    from repro.core.scheduler import Task
    from repro.core.temporal import IntensityTrace
    from repro.resilience import Resilience, ResilientProvider
    from repro.sim import AsyncEngineDriver, PoissonArrivals

    c = EdgeCluster(nodes=[])
    hours24 = np.arange(24.0)
    traces = {}
    for i in range(n_nodes):
        c.add_node(NodeSpec(f"n{i}", cpu=2.0, mem_mb=16000.0,
                            carbon_intensity=80.0 + 55.0 * i))
        vals = 80.0 + 55.0 * i + 60.0 * np.sin(
            2.0 * np.pi * (hours24 / 24.0 + i / n_nodes))
        traces[f"n{i}"] = IntensityTrace(
            f"r{i}", tuple(float(v) for v in vals))
    base = TraceProvider(traces)
    provider = ResilientProvider(base) if resilient else base
    if resilient:
        # seed the last-known-good cache so a blackout at hour 0 degrades
        # instead of KeyError-ing
        provider.intensity_batch(list(c.nodes), 0.0)
    res = Resilience(max_attempts=4, backoff_base_hours=0.005) \
        if resilient else None
    eng = CarbonEdgeEngine(c, provider=provider, resilience=res,
                           batch_execute=batch_execute)
    drv = AsyncEngineDriver(
        eng, PoissonArrivals(rate_per_hour=400.0, seed=seed),
        lambda uid, hour: Task(cpu=0.1, mem_mb=64.0, base_latency_ms=60.0),
        horizon_hours=horizon, max_batch=16, slo_latency_s=1.0,
        faults=faults)
    m = drv.run()
    return m, eng


def _make_faults(n_nodes: int, horizon: float, crash_rate: float,
                 outage_rate: float, seed: int):
    from repro.resilience import FaultInjector

    return FaultInjector.generate(
        [f"n{i}" for i in range(n_nodes)], horizon, seed=seed,
        crash_rate_per_hour=crash_rate, mttr_hours=0.06,
        detect_delay_hours=0.02,
        outage_rate_per_hour=outage_rate, outage_hours=0.08,
        straggle_rate_per_hour=crash_rate / 2.0, straggle_hours=0.05)


def churn_cell(crash_rate: float, outage_rate: float, *,
               n_nodes: int = 8, horizon: float = 0.5,
               seed: int = 3) -> Dict:
    """One sweep cell: the lagged-detection run vs its fault oracle."""
    inj = _make_faults(n_nodes, horizon, crash_rate, outage_rate, seed)
    m, eng = _sim(inj, n_nodes=n_nodes, horizon=horizon)
    s = m.summary()
    dead = sum(m.dead.values())
    submitted = s["tasks"] + dead
    # oracle: identical fault windows, zero detection lag (fresh injector
    # — one injector carries restore state for exactly one run)
    oracle_inj = _make_faults(n_nodes, horizon, crash_rate, outage_rate,
                              seed).without_detection_lag()
    mo, _ = _sim(oracle_inj, n_nodes=n_nodes, horizon=horizon)
    so = mo.summary()
    oracle_per_task = (so["carbon_g_per_task"] if so["tasks"] else 0.0)
    regret = (s["carbon_g_per_task"] / oracle_per_task - 1.0
              if oracle_per_task else 0.0)
    return {
        "crash_rate_per_hour": crash_rate,
        "outage_rate_per_hour": outage_rate,
        "fleet_availability": inj.fleet_availability(n_nodes, horizon),
        "request_availability": (s["tasks"] / submitted
                                 if submitted else 1.0),
        "completed": s["tasks"],
        "dead_letters": dead,
        "retries_total": eng.report()["outcomes"].get("retry", 0),
        "slo_violation_rate": s["slo_violation_rate"],
        "mttr_hours": inj.mttr_hours(),
        "carbon_g_per_task": s["carbon_g_per_task"],
        "oracle_carbon_g_per_task": oracle_per_task,
        "carbon_regret_vs_oracle": regret,
        "contact_failures": sum(
            eng.resilience.health.fails_total.values()),
    }


def byte_identity() -> Dict:
    """Zero-fault schedule -> byte-identical to a resilience-free run on
    both execute paths; fixed fault seed -> byte-identical repeats."""
    from repro.resilience import FaultInjector

    out = {}
    for batch_execute in (True, False):
        key = "batched" if batch_execute else "scalar"
        golden = _sim(None, resilient=False,
                      batch_execute=batch_execute)[0].to_text()
        wired = _sim(FaultInjector.scripted([]), resilient=True,
                     batch_execute=batch_execute)[0].to_text()
        out[f"{key}_zero_fault_match"] = wired == golden
    a = _sim(_make_faults(8, 0.5, 3.0, 1.0, 7))[0].to_text()
    b = _sim(_make_faults(8, 0.5, 3.0, 1.0, 7))[0].to_text()
    out["fault_seed_repeat_match"] = a == b
    return out


def bench_overhead(n: int, b: int, *, reps: int, seed: int = 0) -> Dict:
    """Interleaved zero-fault ``engine.step``: resilience attached vs
    bare. Median of adjacent-pair ratios (same estimator as the obs
    gate) — each pair runs back-to-back under the same machine state."""
    from repro.core.api import CarbonEdgeEngine
    from repro.resilience import Resilience

    eng_off = CarbonEdgeEngine(make_fleet(n, seed=seed))
    eng_on = CarbonEdgeEngine(make_fleet(n, seed=seed),
                              resilience=Resilience())
    tasks = make_tasks(b, seed=seed)
    eng_off.submit_many(tasks)
    off_nodes = [r.node for r in eng_off.step()]   # warm (caches, memo)
    eng_on.submit_many(tasks)
    on_nodes = [r.node for r in eng_on.step()]
    assert on_nodes == off_nodes, \
        "attached resilience changed a zero-fault scheduling decision"
    offs, ons = [], []
    for _ in range(reps):
        eng_off.submit_many(tasks)
        t0 = time.perf_counter()
        eng_off.step()
        offs.append(time.perf_counter() - t0)
        eng_on.submit_many(tasks)
        t0 = time.perf_counter()
        eng_on.step()
        ons.append(time.perf_counter() - t0)
    pair = sorted(on / off for on, off in zip(ons, offs))
    return {
        "n_nodes": n, "batch": b, "reps": reps,
        "bare_step_ms": min(offs) * 1e3,
        "resilient_step_ms": min(ons) * 1e3,
        "overhead_x": pair[len(pair) // 2],
        "overhead_best_x": min(ons) / min(offs),
    }


def run(smoke: bool = False,
        out_path: str = "BENCH_resilience.json") -> Dict:
    cells = []
    for crash_rate, outage_rate in (SMOKE_CELLS if smoke else FULL_CELLS):
        cell = churn_cell(crash_rate, outage_rate)
        cells.append(cell)
        print(f"churn {crash_rate:4.1f}/h outage {outage_rate:4.1f}/h: "
              f"avail {cell['request_availability']:.4f} "
              f"slo_viol {cell['slo_violation_rate']:.4f} "
              f"dead {cell['dead_letters']:3d} "
              f"mttr {cell['mttr_hours']*60:5.1f} min "
              f"regret {cell['carbon_regret_vs_oracle']:+.4f}")
    identity = byte_identity()
    print("byte-identity:", identity)
    n, b = OVERHEAD_ROW
    overhead = bench_overhead(n, b, reps=20 if smoke else 40)
    print(f"overhead N={n} B={b}: bare {overhead['bare_step_ms']:.3f} ms "
          f"resilient {overhead['resilient_step_ms']:.3f} ms "
          f"({overhead['overhead_x']:.3f}x)")
    out = {"cells": cells, "byte_identity": identity,
           "overhead": overhead, "smoke": smoke,
           "overhead_bound_x": OVERHEAD_BOUND_X,
           "availability_floor": AVAILABILITY_FLOOR}
    with open(out_path, "w") as f:
        json.dump(out, f, indent=2)
    print(f"wrote {out_path}")
    return out


def main(smoke: bool = False):
    return run(smoke=smoke)


if __name__ == "__main__":
    import sys

    main(smoke="--smoke" in sys.argv)
