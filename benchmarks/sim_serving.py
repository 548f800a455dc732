"""Beyond-paper: serving under load dynamics (repro.sim, DESIGN.md §2).

The paper evaluates static 50-inference batches; this benchmark drives the
same engine through the discrete-event simulator and sweeps the axis the
paper cannot express — *time*:

- arrival rate x mode: queueing delay and carbon per task as utilisation
  grows (Poisson arrivals, duck-curve grid);
- forecast error x deferral: deferrable evening workload planned through a
  biased persistence forecast; the ``regret_g`` column is realized carbon
  minus the perfect-forecast oracle's, and must grow monotonically with
  the forecast bias (CarbonCP-style acting-under-uncertainty);
- static parity: a constant-rate arrival process over a StaticProvider
  must reproduce the paper-scenario engine numbers exactly (Table II/IV/V
  are a special case of the simulator, not a separate code path).
"""
from __future__ import annotations

from typing import Dict, List

from repro.core.api import CarbonEdgeEngine
from repro.core.cluster import EdgeCluster, PAPER_NODES
from repro.core.providers import (ForecastProvider, StaticProvider,
                                  TraceProvider)
from repro.core.scheduler import Task
from repro.core.temporal import DeferrableTask, synthetic_trace
from repro.sim import AsyncEngineDriver, ConstantRateArrivals, PoissonArrivals

EVENING_HOUR = 17.0          # submissions start on the evening ramp
BASE_LATENCY_MS = 250.0
SEED = 7


def duck_traces() -> Dict[str, object]:
    return {
        "node-high": synthetic_trace("coal-heavy", 620.0, solar_dip=0.1),
        "node-medium": synthetic_trace("cn-average", 530.0, solar_dip=0.3),
        "node-green": synthetic_trace("hydro-rich", 380.0, solar_dip=0.5),
    }


def make_engine(mode: str, time_varying: bool = True) -> CarbonEdgeEngine:
    c = EdgeCluster(nodes=PAPER_NODES, host_power_w=142.0)
    c.profile(BASE_LATENCY_MS)
    provider = (TraceProvider(duck_traces(),
                              fallback=StaticProvider.from_cluster(c))
                if time_varying else StaticProvider.from_cluster(c))
    return CarbonEdgeEngine(c, mode=mode, provider=provider)


def run_scenario(mode: str, arrivals, *, deferrable_hours: float = 0.0,
                 forecast=None, horizon_hours: float = 2.0,
                 start_hour: float = EVENING_HOUR, max_batch: int = 16,
                 slo_latency_s: float = 2.0) -> Dict:
    engine = make_engine(mode)

    def factory(uid: int, hour: float):
        if deferrable_hours > 0:
            return DeferrableTask(cpu=0.05, mem_mb=16.0,
                                  base_latency_ms=BASE_LATENCY_MS,
                                  deadline_hours=deferrable_hours,
                                  duration_hours=0.25)
        return Task(cpu=0.05, mem_mb=16.0, base_latency_ms=BASE_LATENCY_MS)

    driver = AsyncEngineDriver(engine, arrivals, factory,
                               start_hour=start_hour,
                               horizon_hours=horizon_hours,
                               max_batch=max_batch, forecast=forecast,
                               slo_latency_s=slo_latency_s, tick_hours=1.0)
    m = driver.run()
    return m.summary()


# -- sweep 1: arrival rate x mode -------------------------------------------


def rate_mode_sweep(rates=(2000.0, 8000.0, 12000.0),
                    modes=("green", "performance"),
                    horizon_hours: float = 0.05) -> List[Dict]:
    rows = []
    for mode in modes:
        for rate in rates:
            s = run_scenario(mode, PoissonArrivals(rate, seed=SEED),
                             horizon_hours=horizon_hours)
            rows.append({"mode": mode, "rate_per_hour": rate,
                         "carbon_g_per_task": s["carbon_g_per_task"],
                         "wait_s_p50": s["wait_s_p50"],
                         "wait_s_p95": s["wait_s_p95"],
                         "slo_violation_rate": s["slo_violation_rate"],
                         "wait_histogram": s["wait_histogram"]})
    return rows


# -- sweep 2: forecast error x deferral --------------------------------------


def deferral_sweep(biases=(0.0, 1.0, 2.0, 4.0), rate: float = 60.0,
                   deadline_hours: float = 24.0) -> List[Dict]:
    """Evening-submitted deferrable workload. ``bias`` hours of persistence
    lead on the forecast shifts the planned wake slot off the true solar
    dip; the oracle row is bias 0 (forecast == realized trace)."""
    arrivals = PoissonArrivals(rate, seed=SEED)
    true_provider = TraceProvider(duck_traces())

    run_now = run_scenario("green", arrivals,
                           deferrable_hours=deadline_hours, forecast=None)
    rows = [{"scenario": "run-now", "bias_h": None,
             "carbon_g_total": run_now["carbon_g_total"],
             "deferred_tasks": run_now["deferred_tasks"]}]
    # The oracle is always an explicit bias-0 run (forecast == realized
    # trace), whatever biases the caller sweeps.
    oracle = run_scenario("green", arrivals, deferrable_hours=deadline_hours,
                          forecast=ForecastProvider(true_provider))
    oracle_total = oracle["carbon_g_total"]
    for b in biases:
        s = oracle if b == 0.0 else run_scenario(
            "green", arrivals, deferrable_hours=deadline_hours,
            forecast=ForecastProvider(true_provider, lead_hours=b))
        rows.append({
            "scenario": f"defer(bias={b:g}h)", "bias_h": b,
            "carbon_g_total": s["carbon_g_total"],
            "deferred_tasks": s["deferred_tasks"],
            "savings_vs_run_now_pct": 100.0 * (
                1.0 - s["carbon_g_total"] / run_now["carbon_g_total"]),
            "regret_g": s["carbon_g_total"] - oracle_total,
        })
    return rows


# -- sweep 3: static parity ---------------------------------------------------


def static_parity(iterations: int = 50) -> Dict:
    """The simulator with a constant-rate process and a static provider
    must reproduce the paper-scenario engine run exactly (Table II/IV/V
    numbers are unchanged by the new driver)."""
    ref = CarbonEdgeEngine(
        EdgeCluster(nodes=PAPER_NODES, host_power_w=142.0), mode="green")
    ref.cluster.profile(BASE_LATENCY_MS)
    ref_rep = ref.run(task=Task(cpu=0.05, mem_mb=16.0,
                                base_latency_ms=BASE_LATENCY_MS),
                      iterations=iterations)

    engine = make_engine("green", time_varying=False)
    driver = AsyncEngineDriver(
        engine, ConstantRateArrivals(rate_per_hour=float(iterations)),
        lambda uid, hour: Task(cpu=0.05, mem_mb=16.0,
                               base_latency_ms=BASE_LATENCY_MS),
        start_hour=0.0, horizon_hours=1.0, max_batch=16)
    driver.run()
    sim_rep = engine.report()
    ref_c = ref_rep["totals"]["carbon_g_per_inf"]
    sim_c = sim_rep["totals"]["carbon_g_per_inf"]
    return {
        "ref_carbon_g_per_inf": ref_c,
        "sim_carbon_g_per_inf": sim_c,
        "carbon_match": abs(ref_c - sim_c) < 1e-12,
        "distribution_match": ref_rep["distribution"] == sim_rep["distribution"],
    }


def run() -> Dict:
    return {
        "rate_mode": rate_mode_sweep(),
        "deferral": deferral_sweep(),
        "parity": static_parity(),
    }


def main() -> Dict:
    out = run()
    print(f"{'mode':>12s} {'rate/h':>8s} {'g/task':>9s} {'p50 wait s':>10s} "
          f"{'p95 wait s':>10s} {'slo viol':>8s}")
    for r in out["rate_mode"]:
        print(f"{r['mode']:>12s} {r['rate_per_hour']:8.0f} "
              f"{r['carbon_g_per_task']:9.5f} {r['wait_s_p50']:10.3f} "
              f"{r['wait_s_p95']:10.3f} {r['slo_violation_rate']:8.3f}")
    print(f"\n{'scenario':>16s} {'carbon g':>10s} {'deferred':>8s} "
          f"{'savings %':>9s} {'regret g':>9s}")
    for r in out["deferral"]:
        sav = r.get("savings_vs_run_now_pct")
        reg = r.get("regret_g")
        print(f"{r['scenario']:>16s} {r['carbon_g_total']:10.4f} "
              f"{r['deferred_tasks']:8d} "
              f"{sav if sav is None else format(sav, '9.1f')!s:>9s} "
              f"{reg if reg is None else format(reg, '9.4f')!s:>9s}")
    p = out["parity"]
    print(f"\nstatic parity: carbon_match={p['carbon_match']} "
          f"distribution_match={p['distribution_match']} "
          f"(ref {p['ref_carbon_g_per_inf']:.6f} g/inf, "
          f"sim {p['sim_carbon_g_per_inf']:.6f} g/inf)")
    return out


if __name__ == "__main__":
    main()
