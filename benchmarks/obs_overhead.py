"""Observability overhead: the off-path and on-path cost of repro.obs.

Three claims are measured (DESIGN.md §9, §12) and asserted by
``gate_obs``:

- **off**: an engine built without obs and one built with every pillar
  disabled run the same code path — the disabled engine step stays under
  the same loose absolute backstop as the other gates, and a fixed-seed
  sim renders a byte-identical ``metrics.to_text`` report whether obs is
  absent, disabled, or fully enabled, across both execute paths AND both
  event queues (``byte_identity``);
- **on**: with ALL pillars enabled (decision trace + metrics registry +
  step profiler + journeys + rollups + alerts), the end-to-end
  ``engine.step`` stays within a bounded factor (acceptance: <= 1.3x at
  N=10^4, B=1024) of the disabled path, and never changes a decision.
  Small shapes get an explicit looser bound — see
  ``SMALL_SHAPE_RATIONALE``;
- **journeys/rollups/alerts determinism**: a fixed-seed chaos scenario
  (tenancy + resilience + scripted faults + closed-loop clients, obs
  wired to BOTH the engine and the driver) renders byte-identical
  ``journeys.to_text`` / ``rollups.to_text`` / ``alerts.to_text`` across
  a repeat run and across the calendar/heap event queues, with at least
  one alert actually firing (``journey_determinism``). A 10^5-client
  closed-loop run must export rollups with memory O(windows) — bounded
  by the allocated window capacity, independent of task count
  (``rollup_scale``).

Sweeps (N, B) through the fleet-scale fixtures, reports per-task times
and the enabled/disabled ratio, and writes ``BENCH_obs.json`` including
the enabled run's per-phase profiler summary. The CI smoke runs
``run(smoke=True)`` (which still includes the acceptance row and the
10^5-client scale row); gate assertions live in ``benchmarks/ci_gates.py``
(``python -m benchmarks.ci_gates obs``).
"""
from __future__ import annotations

import json
import time
from typing import Dict

from benchmarks.fleet_scale import make_fleet, make_tasks

# (n_nodes, batch) rows; the (10_000, 1024) acceptance row runs in both
# sweeps — the 1.3x bound is defined there.
FULL_ROWS = ((1_000, 256), (10_000, 1024), (100_000, 1024))
SMOKE_ROWS = ((512, 64), (2_048, 256), (10_000, 1024))

# The acceptance bound, defined at N=10^4 B=1024 where per-task work
# dominates. 1.25 (trace+metrics+profiler, PR 7) + rollup folds (PR 10).
OVERHEAD_BOUND_X = 1.3

# Small shapes (N=512, B=64) amortize the fixed per-step obs cost —
# snapshot assembly, registry scatter set-up, profiler clock reads, one
# rollup fold — over few tasks, so the *ratio* runs hot (~1.8x measured)
# while the absolute cost stays microscopic (the disabled step is
# ~100 us there). The explicit small-shape bound documents that this is
# a fixed-cost artifact, not a scaling problem: the per-task acceptance
# bound above is the claim that matters at fleet scale.
SMALL_SHAPE_BOUND_X = 2.5
SMALL_SHAPE_RATIONALE = (
    "fixed per-step obs cost (snapshot + registry scatter set-up + "
    "profiler clocks + one rollup fold) amortized over <=64 tasks; "
    "absolute overhead is microseconds while the ratio runs ~1.8x")


def bench_row(n: int, b: int, *, reps: int, seed: int = 0) -> Dict:
    """Best-of-reps e2e ``engine.step``: obs fully enabled vs disabled.
    The two engines step in alternation so both paths see the same
    machine state (CPU frequency, caches) — the ratio is what the gate
    bounds, and block-sequenced timing would let drift between blocks
    masquerade as overhead."""
    from repro.core.api import CarbonEdgeEngine
    from repro.obs import Observability

    obs = Observability.all()
    eng_off = CarbonEdgeEngine(make_fleet(n, seed=seed))
    eng_on = CarbonEdgeEngine(make_fleet(n, seed=seed), obs=obs)
    tasks = make_tasks(b, seed=seed)
    eng_off.submit_many(tasks)
    off_nodes = [r.node for r in eng_off.step()]   # warm (caches, memo)
    eng_on.submit_many(tasks)
    on_nodes = [r.node for r in eng_on.step()]
    assert on_nodes == off_nodes, \
        "enabled observability changed a scheduling decision"
    offs = []
    ons = []
    for _ in range(reps):
        eng_off.submit_many(tasks)
        t0 = time.perf_counter()
        eng_off.step()
        offs.append(time.perf_counter() - t0)
        eng_on.submit_many(tasks)
        t0 = time.perf_counter()
        eng_on.step()
        ons.append(time.perf_counter() - t0)
    off_s, on_s = min(offs), min(ons)
    # the gated estimator: median of per-adjacent-pair ratios — each pair
    # ran back-to-back under the same machine state, and the median drops
    # the scheduler-noise outliers that a ratio-of-minima can still catch
    pair = sorted(on / off for on, off in zip(ons, offs))
    overhead_x = pair[len(pair) // 2]
    steps = reps + 1
    assert obs.trace.count == steps * b, (obs.trace.count, steps, b)
    for phase in ("select", "execute", "bill", "observe"):
        assert obs.profiler.count(phase) == steps, (phase, steps)
    # the engine folds every successful step into the rollup store too
    assert obs.rollups.n_windows >= 1
    assert int(obs.rollups.tasks[:1].sum()) == steps * b
    return {
        "n_nodes": n, "batch": b, "steps": steps,
        "disabled_step_ms": off_s * 1e3,
        "enabled_step_ms": on_s * 1e3,
        "disabled_per_task_ms": off_s * 1e3 / b,
        "enabled_per_task_ms": on_s * 1e3 / b,
        "overhead_x": overhead_x,
        "overhead_best_x": on_s / off_s,
        "trace_rows": obs.trace.count,
        "profiler": obs.profiler.summary(),
    }


def sim_byte_identity() -> Dict:
    """Fixed-seed sim ``to_text`` byte-equality: obs absent vs disabled vs
    fully enabled, across the batched/scalar execute paths AND the
    calendar/heap event queues."""
    from repro.core.api import CarbonEdgeEngine
    from repro.core.cluster import EdgeCluster, PAPER_NODES
    from repro.core.providers import (ForecastProvider, StaticProvider,
                                      TraceProvider)
    from repro.core.scheduler import Task
    from repro.core.temporal import DeferrableTask, synthetic_trace
    from repro.obs import Observability
    from repro.sim import AsyncEngineDriver, PoissonArrivals

    def one(obs, batch_execute, event_queue):
        c = EdgeCluster(nodes=PAPER_NODES, host_power_w=142.0)
        c.profile(250.0)
        provider = TraceProvider(
            {"node-high": synthetic_trace("coal-heavy", 620.0,
                                          solar_dip=0.1),
             "node-medium": synthetic_trace("cn-average", 530.0,
                                            solar_dip=0.3),
             "node-green": synthetic_trace("hydro-rich", 380.0,
                                           solar_dip=0.5)},
            fallback=StaticProvider.from_cluster(c))
        eng = CarbonEdgeEngine(c, mode="green", provider=provider,
                               batch_execute=batch_execute, obs=obs)

        def factory(uid, hour):
            if uid % 3 == 0:
                return DeferrableTask(cpu=0.05, mem_mb=16.0,
                                      base_latency_ms=250.0,
                                      deadline_hours=4.0)
            return Task(cpu=0.05, mem_mb=16.0, base_latency_ms=250.0)

        d = AsyncEngineDriver(eng,
                              PoissonArrivals(rate_per_hour=240.0, seed=11),
                              factory, horizon_hours=1.0, max_batch=16,
                              forecast=ForecastProvider(provider),
                              tick_hours=0.25, slo_latency_s=2.0, obs=obs,
                              event_queue=event_queue)
        return d.run().to_text()

    out = {}
    for batch_execute in (True, False):
        path = "batched" if batch_execute else "scalar"
        for queue in ("calendar", "heap"):
            golden = one(None, batch_execute, queue)
            out[f"{path}_{queue}_disabled_match"] = \
                one(Observability(), batch_execute, queue) == golden
            out[f"{path}_{queue}_enabled_match"] = \
                one(Observability.all(), batch_execute, queue) == golden
    return out


def _chaos_driver(obs, event_queue: str):
    """The fixed-seed chaos scenario (examples/chaos_serving.py):
    two closed-loop tenants through a lagged-detection node crash + feed
    blackout, obs wired to BOTH the engine and the driver."""
    from repro.core.api import CarbonEdgeEngine
    from repro.core.cluster import EdgeCluster, PAPER_NODES
    from repro.core.providers import StaticProvider
    from repro.resilience import (Fault, FaultInjector, Resilience,
                                  ResilientProvider)
    from repro.sim import (AsyncEngineDriver, ClientPopulation,
                           ClosedLoopClientPool)
    from repro.tenancy import TenantPolicy, TenantRegistry, TenantSpec
    from repro.tenancy.spec import TenantTask

    faults = [Fault(0.004, "crash", "node-green", detected=False),
              Fault(0.008, "detect", "node-green"),
              Fault(0.010, "blackout"),
              Fault(0.016, "restore"),
              Fault(0.020, "recover", "node-green")]
    cluster = EdgeCluster(nodes=PAPER_NODES, host_power_w=142.0)
    cluster.profile(250.0)
    provider = ResilientProvider(StaticProvider(
        {n: cluster.nodes[n].spec.carbon_intensity for n in cluster.nodes}))
    registry = TenantRegistry([
        TenantSpec("gold", mode="green", priority=2, allowance_g=0.05,
                   period_hours=0.25),
        TenantSpec("batch", mode="green")])
    engine = CarbonEdgeEngine(
        cluster, mode="green", policy=TenantPolicy(registry=registry),
        provider=provider,
        resilience=Resilience(max_attempts=3, backoff_base_hours=0.002),
        obs=obs)
    pool = ClosedLoopClientPool(
        [ClientPopulation("gold", 6, mean_think_hours=0.0008,
                          slo_latency_s=2.0, priority=2),
         ClientPopulation("batch", 4, mean_think_hours=0.002,
                          slo_latency_s=10.0)],
        seed=4)
    return AsyncEngineDriver(
        engine, None,
        lambda uid, hour, tenant: TenantTask(cpu=0.05, mem_mb=16.0,
                                             base_latency_ms=250.0,
                                             tenant=tenant),
        horizon_hours=0.03, max_batch=8, slo_latency_s=5.0, clients=pool,
        faults=FaultInjector.scripted(faults), obs=obs,
        event_queue=event_queue)


def journey_determinism():
    """Byte-determinism of the three new pillars on the chaos scenario:
    journeys/rollups/alerts ``to_text`` identical across a repeat run
    and across the calendar/heap event queues, with the enabled run's
    ``metrics.to_text`` still byte-identical to the obs-absent golden
    on both queues, and at least one alert firing (a vacuously empty
    alert stream would make the determinism claim meaningless)."""
    from repro.obs import Observability
    from repro.obs.alerts import default_rules

    def enabled():
        return Observability.all(
            rollup_window_hours=0.005,
            alert_rules=default_rules(availability_floor=0.9, min_tasks=4))

    def run(obs, queue):
        d = _chaos_driver(obs, queue)
        return d.run().to_text()

    texts = {}
    stats = {}
    for label, queue in (("cal_a", "calendar"), ("cal_b", "calendar"),
                         ("heap", "heap")):
        obs = enabled()
        metrics_text = run(obs, queue)
        texts[label] = {"journeys": obs.journeys.to_text(),
                        "rollups": obs.rollups.to_text(),
                        "alerts": obs.alerts.to_text(),
                        "metrics": metrics_text}
        if label == "cal_a":
            cp = obs.journeys.critical_path()
            stats = {"journeys": obs.journeys.max_uid,
                     "states": obs.journeys.state_counts(),
                     "windows": obs.rollups.n_windows,
                     "alert_events": len(obs.alerts.events),
                     "phase_identity_max_abs_err_h":
                         cp["identity_max_abs_err_h"]}
    golden_cal = run(None, "calendar")
    golden_heap = run(None, "heap")
    out = {}
    for surface in ("journeys", "rollups", "alerts"):
        out[f"{surface}_repeat_match"] = \
            texts["cal_a"][surface] == texts["cal_b"][surface]
        out[f"{surface}_queue_match"] = \
            texts["cal_a"][surface] == texts["heap"][surface]
    out["chaos_metrics_calendar_match"] = \
        texts["cal_a"]["metrics"] == golden_cal
    out["chaos_metrics_heap_match"] = texts["heap"]["metrics"] == golden_heap
    out["alerts_fired"] = stats["alert_events"] > 0
    out["phase_identity_ok"] = \
        stats["phase_identity_max_abs_err_h"] < 1e-9
    return out, stats


def rollup_scale_row(n_clients: int = 100_000,
                     horizon_hours: float = 0.03) -> Dict:
    """A 10^5-client closed-loop run (the PR 9 null-executor scenario, so
    the row times obs folding rather than engine scoring) with rollups +
    alerts enabled: the rollup store must export with memory O(windows) —
    bounded by the allocated window capacity and tenant count, independent
    of how many tasks streamed through it."""
    from repro.obs import Observability
    from benchmarks.sim_scale import _null_driver

    obs = Observability(trace=False, metrics=False, profile=False,
                        journeys=False, rollups=True, alerts=True,
                        rollup_window_hours=0.002)
    drv = _null_driver(n_clients, horizon_hours, "calendar")
    drv.obs = obs
    t0 = time.perf_counter()
    m = drv.run()
    wall = time.perf_counter() - t0
    roll = obs.rollups
    exported = roll.export()
    assert len(exported["tasks"]) == roll.n_windows
    # O(windows) memory: 5 f8/i8 scalar columns + the (5,) verdict row +
    # one f8 per tenant per window — a loose 256 B/window bound with a
    # page of slack, nothing proportional to task count
    cap_bytes = 256 * roll.capacity + 4096
    return {
        "n_clients": n_clients,
        "horizon_hours": horizon_hours,
        "events": drv.events_processed,
        "tasks": m.n_records,
        "windows": roll.n_windows,
        "rollup_nbytes": roll.nbytes,
        "memory_ok": bool(roll.nbytes <= cap_bytes),
        "wall_s": round(wall, 4),
        "rollup_on_per_event_us": round(
            wall / max(1, drv.events_processed) * 1e6, 4),
    }


def run(smoke: bool = False, out_path: str = "BENCH_obs.json") -> Dict:
    rows = []
    for n, b in (SMOKE_ROWS if smoke else FULL_ROWS):
        # the acceptance row gets the most pairs — the median estimator
        # tightens with sample count and each pair costs ~2.5 ms there
        reps = 20 if n <= 2_048 else (40 if n <= 10_000 else 5)
        row = bench_row(n, b, reps=reps)
        rows.append(row)
        print(f"obs e2e N={n:>7} B={b:>5}: off {row['disabled_step_ms']:7.3f}"
              f" ms  on {row['enabled_step_ms']:7.3f} ms"
              f"  ({row['overhead_x']:5.2f}x,"
              f" {row['enabled_per_task_ms']*1e3:7.2f} us/task on)")
    identity = sim_byte_identity()
    print("sim byte-identity:", identity)
    journeys, journey_stats = journey_determinism()
    print("journey determinism:", journeys)
    print("journey stats:", journey_stats)
    scale = rollup_scale_row(n_clients=100_000)
    print(f"rollup scale: {scale['tasks']} tasks over {scale['windows']} "
          f"windows in {scale['rollup_nbytes']} B "
          f"(memory_ok={scale['memory_ok']})")
    out = {"rows": rows, "byte_identity": identity,
           "journey_determinism": journeys,
           "journey_stats": journey_stats,
           "rollup_scale": scale, "smoke": smoke,
           "overhead_bound_x": OVERHEAD_BOUND_X,
           "small_shape_bound_x": SMALL_SHAPE_BOUND_X,
           "small_shape_rationale": SMALL_SHAPE_RATIONALE}
    with open(out_path, "w") as f:
        json.dump(out, f, indent=2)
    print(f"wrote {out_path}")
    return out


def main(smoke: bool = False):
    return run(smoke=smoke)


if __name__ == "__main__":
    import sys

    main(smoke="--smoke" in sys.argv)
