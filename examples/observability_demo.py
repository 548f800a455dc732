"""Observability walkthrough: why did task X land on node Y at cut Z?

Runs a partition-aware engine with every repro.obs pillar enabled,
then answers the operator questions the subsystem exists for (DESIGN.md
§9, §12): per-task decision forensics from the trace ring (winning
score vs runner-up, forecast interval, carbon billed), Prometheus-style
metrics exposition, per-phase step timing, a deterministic JSONL
export — then a closed-loop chaos drill to walk one request's full
causal journey (arrival -> parks -> failover -> execute), the windowed
rollup series, and the alert fire/resolve log.

Run:  PYTHONPATH=src python examples/observability_demo.py
"""
import numpy as np

from repro.core.api import CarbonEdgeEngine
from repro.core.cluster import PAPER_NODES, EdgeCluster
from repro.core.providers import StaticProvider
from repro.core.scheduler import Task
from repro.obs import Observability
from repro.partition.policy import PartitionPolicy
from repro.partition.profile import profile_costs

# -- a cluster, a partition-aware policy, and obs fully on ------------------
cluster = EdgeCluster(nodes=PAPER_NODES, host_power_w=142.0)
cluster.profile(250.0)

# 4-layer toy model: equal compute, one cheap boundary after layer 2
profile = profile_costs([12.0, 12.0, 12.0, 12.0],
                        boundary_bytes=[4e5, 1e3, 4e5, 0.0])
policy = PartitionPolicy(profile)

obs = Observability.all()          # trace + metrics + profiler
eng = CarbonEdgeEngine(cluster, mode="green", policy=policy, obs=obs)

rng = np.random.default_rng(7)
for step in range(4):
    eng.submit_many([Task(cpu=float(c), mem_mb=32.0, base_latency_ms=250.0)
                     for c in rng.choice([0.05, 0.2, 0.6], size=32)])
    eng.step()

# -- 1. decision forensics: why did task i go where it went? ----------------
trace = obs.trace
print(f"=== trace: {trace.count} decisions recorded ===")
row = trace.row(len(trace) - 1)          # most recent decision
print(trace.explain(row["step"], row["task"]))
if row["score"] is not None and row["runner_up"] is not None:
    margin = row["score"] - row["runner_up"]
    print(f"won by a margin of {margin:.4f} score units over the "
          f"runner-up\n")

# -- 2. aggregates straight off the columns ---------------------------------
print("verdicts:", trace.verdict_counts())
print("cut histogram (cut index -> tasks):", trace.cut_histogram())

# -- 3. metrics: Prometheus exposition --------------------------------------
print("\n=== metrics (exposition excerpt) ===")
text = obs.metrics.to_text()
for line in text.splitlines():
    if line.startswith(("engine_tasks_total", "engine_carbon_g_total",
                        "engine_outcomes_total")):
        print(line)

# -- 4. profiler: where did the step time go? -------------------------------
print("\n=== per-phase step timing ===")
for phase, s in sorted(obs.profiler.summary()["phases"].items()):
    print(f"{phase:10s} n={s['count']:3d}  total={s['total_s']*1e3:7.3f} ms"
          f"  p50={s['p50_s']*1e6:7.1f} us  p95={s['p95_s']*1e6:7.1f} us")

# -- 5. deep report + deterministic export ----------------------------------
rep = eng.report(deep=True)
print("\noutcome totals:", rep["outcomes"],
      " deferred depth:", rep["deferred_depth"])
path = "/tmp/obs_trace.jsonl"
n = trace.export_jsonl(path)
print(f"exported {n} trace rows to {path} (deterministic for a fixed seed)")

# -- 6. journeys: one request's whole causal path (DESIGN.md §12) -----------
# A closed-loop chaos drill (node crash with lagged detection, then
# recovery) with the obs hub wired to BOTH the engine and the driver:
# journeys record the per-request life, rollups fold the run into
# fixed-width windows, alerts turn the windows into fire/resolve events.
from repro.obs import default_rules
from repro.resilience import (Fault, FaultInjector, Resilience,
                              ResilientProvider)
from repro.sim import (AsyncEngineDriver, ClientPopulation,
                       ClosedLoopClientPool)
from repro.tenancy import TenantPolicy, TenantRegistry, TenantSpec
from repro.tenancy.spec import TenantTask

cluster2 = EdgeCluster(nodes=PAPER_NODES, host_power_w=142.0)
cluster2.profile(250.0)
provider = ResilientProvider(StaticProvider(
    {n: cluster2.nodes[n].spec.carbon_intensity for n in cluster2.nodes}))
obs2 = Observability.all(
    rollup_window_hours=0.005,
    alert_rules=default_rules(availability_floor=0.9, min_tasks=4))
eng2 = CarbonEdgeEngine(
    cluster2, mode="green",
    policy=TenantPolicy(registry=TenantRegistry(
        [TenantSpec("gold", mode="green", priority=2),
         TenantSpec("batch", mode="green")])),
    provider=provider,
    resilience=Resilience(max_attempts=3, backoff_base_hours=0.002),
    obs=obs2)
pool = ClosedLoopClientPool(
    [ClientPopulation("gold", 6, mean_think_hours=0.0008,
                      slo_latency_s=2.0, priority=2),
     ClientPopulation("batch", 4, mean_think_hours=0.002,
                      slo_latency_s=10.0)],
    seed=4)
driver = AsyncEngineDriver(
    eng2, None,
    lambda uid, hour, tenant: TenantTask(cpu=0.05, mem_mb=16.0,
                                         base_latency_ms=250.0,
                                         tenant=tenant),
    horizon_hours=0.03, max_batch=8, slo_latency_s=5.0, clients=pool,
    faults=FaultInjector.scripted([
        Fault(0.004, "crash", "node-green", detected=False),
        Fault(0.008, "detect", "node-green"),
        Fault(0.020, "recover", "node-green")]),
    obs=obs2)
driver.run()

jt = obs2.journeys
print(f"\n=== journeys: {jt.max_uid} requests, "
      f"states {jt.state_counts()} ===")
# explain the most-drained completed request — the one with the most
# eventful causal path through the drill
busiest = max((u for u in range(1, jt.max_uid + 1)
               if jt.state[u] == 1), key=lambda u: int(jt.drains[u]))
print(jt.explain_journey(busiest))

cp = jt.critical_path()
print(f"\ncritical path over {cp['journeys']} completed journeys "
      f"(phase shares of e2e):")
for phase in ("plan_defer", "queue_wait", "budget_defer",
              "retry_backoff", "service"):
    print(f"  {phase:14s} {cp[f'{phase}_share']:6.1%}")
print(f"  phase-sum identity residual: "
      f"{cp['identity_max_abs_err_h']:.3g} h")

# -- 7. rollups: the run as O(windows) series -------------------------------
roll = obs2.rollups
print(f"\n=== rollups: {roll.n_windows} windows of "
      f"{roll.window_hours * 60:.1f} min (store is {roll.nbytes} B) ===")
for line in roll.to_text().splitlines()[:4]:
    print(" ", line)

# -- 8. alerts: windows -> deterministic fire/resolve events ----------------
print("\n=== alert events ===")
for ev in obs2.alerts.events:
    print(" ", ev.render())
print("active at end of run:", obs2.alerts.active or "none")
