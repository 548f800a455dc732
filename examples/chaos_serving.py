"""Chaos drill: closed-loop tenants through a node crash + feed blackout.

A scripted fault scenario (DESIGN.md §10) hits a two-tenant closed-loop
deployment: the greenest node crashes with a detection lag (the
scheduler keeps placing onto it until it is caught by contact or by the
detector), then the carbon feed blacks out (reads degrade to
last-known-good values with staleness-widened intervals), then both
recover. The run keeps serving throughout — contact failures fail over
via one batched re-selection, hopeless tasks dead-letter after the
retry cap instead of looping — and the decision trace can explain a
failover placement after the fact.

Run:  PYTHONPATH=src python examples/chaos_serving.py
"""
from repro.core.api import CarbonEdgeEngine
from repro.core.cluster import EdgeCluster, PAPER_NODES
from repro.core.providers import StaticProvider
from repro.obs import Observability
from repro.resilience import (Fault, FaultInjector, Resilience,
                              ResilientProvider)
from repro.sim import (AsyncEngineDriver, ClientPopulation,
                       ClosedLoopClientPool)
from repro.tenancy import TenantPolicy, TenantRegistry, TenantSpec
from repro.tenancy.spec import TenantTask

BASE_MS = 250.0

# -- the script: crash (lagged detection) -> blackout -> full recovery ------
FAULTS = [
    Fault(0.004, "crash", "node-green", detected=False),  # ground truth only
    Fault(0.008, "detect", "node-green"),                 # detector catches up
    Fault(0.010, "blackout"),                             # carbon feed dark
    Fault(0.016, "restore"),
    Fault(0.020, "recover", "node-green"),
]

cluster = EdgeCluster(nodes=PAPER_NODES, host_power_w=142.0)
cluster.profile(BASE_MS)
provider = ResilientProvider(StaticProvider(
    {n: cluster.nodes[n].spec.carbon_intensity for n in cluster.nodes}))
registry = TenantRegistry([TenantSpec("gold", mode="green", priority=2),
                           TenantSpec("batch", mode="green")])
res = Resilience(max_attempts=3, backoff_base_hours=0.002)
obs = Observability.all()
engine = CarbonEdgeEngine(cluster, mode="green",
                          policy=TenantPolicy(registry=registry),
                          provider=provider, resilience=res, obs=obs)

pool = ClosedLoopClientPool(
    [ClientPopulation("gold", 6, mean_think_hours=0.0008,
                      slo_latency_s=2.0, priority=2),
     ClientPopulation("batch", 4, mean_think_hours=0.002,
                      slo_latency_s=10.0)],
    seed=4)
driver = AsyncEngineDriver(
    engine, None,
    lambda uid, hour, tenant: TenantTask(cpu=0.05, mem_mb=16.0,
                                         base_latency_ms=BASE_MS,
                                         tenant=tenant),
    horizon_hours=0.03, max_batch=8, slo_latency_s=5.0, clients=pool,
    faults=FaultInjector.scripted(FAULTS))
metrics = driver.run()

# -- phase-by-phase: where did the work land? -------------------------------
PHASES = [("healthy", 0.0, 0.004), ("crash undetected", 0.004, 0.008),
          ("crash detected", 0.008, 0.020), ("recovered", 0.020, 0.03)]
print("placements per phase (node-green is the crashed node):")
for label, lo, hi in PHASES:
    recs = [r for r in metrics.records if lo <= r.start_hour < hi]
    on_green = sum(1 for r in recs if r.node == "node-green")
    print(f"  {label:16s} tasks={len(recs):3d}  on node-green={on_green:3d}")

# -- the failover, explained from the trace ---------------------------------
crash, recover = FAULTS[0].hour, FAULTS[-1].hour
fail_row = next((r for r in obs.trace.rows()
                 if crash <= r["hour"] < recover and r["verdict"] == "done"
                 and r["node"] != "node-green"), None)
if fail_row is not None:
    print("\none failover decision, explained:")
    print(" ", obs.trace.explain(fail_row["step"], fail_row["task"]))
print("verdicts:", obs.trace.verdict_counts())

# -- degraded-mode + recovery accounting ------------------------------------
rep = engine.report()
print("\nresilience report:", rep["resilience"])
print(f"provider reads served stale during the blackout: "
      f"{provider.served_stale}")
print(f"dead-letters: {len(engine.dead_letters)} "
      f"(sim counted: {dict(metrics.dead) or 0})")
inj = FaultInjector.scripted(FAULTS)
print(f"schedule MTTR: {inj.mttr_hours() * 60:.1f} min "
      f"(one crash window of {(recover - crash) * 60:.1f} min)")
s = metrics.summary()
print(f"\nserved {s['tasks']} requests through the drill: "
      f"p95 latency {s['latency_s_p95']:.2f} s, "
      f"SLO violation rate {s['slo_violation_rate']:.3f}, "
      f"{s['carbon_g_per_task'] * 1e3:.3f} mg CO2/task")
