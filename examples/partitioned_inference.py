"""Joint partition+placement with a conformal carbon interval (DESIGN.md §8).

Where ``examples/partition_and_schedule.py`` splits a model across a *fixed*
node list, this example lets the scheduler choose the **(cut, node) pair**:
run layers [0, c) on the requesting device, offload layers [c, L) to the
best-scoring fleet node under the paper's Eq. 3 rule. The cut profile is
derived once per model (Eq. 5 costs + activation bytes for CNNs, per-block
FLOPs for transformers); cut 0 is full offload, so the joint decision can
only match or beat the cut-unaware scheduler.

The carbon estimate is then *interval-bounded*: a split-conformal calibrator
(forecast-vs-actual residuals over a held-out window) turns the point
forecast into a band with finite-sample >= 90% coverage, so the printed
estimate is "lo .. hi gCO2", not a single gamble on the forecast.

Run:  PYTHONPATH=src python examples/partitioned_inference.py
"""
import numpy as np

from repro.configs.cnn_zoo import get_cnn_config
from repro.configs.registry import get_config
from repro.core.cluster import EdgeCluster, NodeSpec
from repro.core.providers import ForecastProvider, TraceProvider
from repro.core.scheduler import MODES, Task
from repro.core.temporal import synthetic_trace
from repro.partition import (PartitionPolicy, calibrate_intensity,
                             joint_time_energy, profile_cnn,
                             profile_transformer)

# -- heterogeneous fleet: the paper's three scenarios + two edge boxes ------
NODES = (
    NodeSpec("node-high", 1.0, 1024, 620.0, region="coal-heavy"),
    NodeSpec("node-medium", 0.6, 512, 530.0, region="cn-average"),
    NodeSpec("node-green", 0.4, 512, 380.0, region="hydro-rich"),
    NodeSpec("edge-pi", 0.25, 256, 120.0, power_w=6.5, region="solar-local"),
    NodeSpec("edge-nuc", 0.5, 512, 260.0, power_w=28.0, region="wind-mix"),
)
cluster = EdgeCluster(nodes=NODES)
cluster.profile(250.0)
task = Task(cpu=0.1, mem_mb=64.0, base_latency_ms=250.0)
NOW = 10.0  # 10:00 — mid-morning grid

# -- conformal band: calibrate the forecast against a noisy actual grid ----
actual = TraceProvider({n.name: synthetic_trace(n.region, n.carbon_intensity,
                                                noise=0.08, seed=i)
                        for i, n in enumerate(NODES)})
point = ForecastProvider(TraceProvider(
    {n.name: synthetic_trace(n.region, n.carbon_intensity)
     for n in NODES}), smoothing_hours=2.0)
names = [n.name for n in NODES]
cal_hours = np.arange(0.0, 24.0, 0.25)          # held-out calibration window
conf = calibrate_intensity(point, actual, names, cal_hours)
forecast = ForecastProvider(point.base, smoothing_hours=2.0, conformal=conf)
print(f"split-conformal 90% band: +/- {conf.quantile(0.9):.1f} gCO2/kWh "
      f"({conf.n} residuals)\n")

# -- joint (cut, node) decisions per model, green vs performance -----------
profiles = (profile_cnn(get_cnn_config("mobilenetv2"), batch=1),
            profile_transformer(get_config("zamba2-2.7b"), seq=512, batch=1))
for prof in profiles:
    print(f"{prof.name}: {prof.num_cuts} candidate cuts")
    for mode in ("green", "performance"):
        policy = PartitionPolicy(prof, backend="numpy")
        d = policy.decide(cluster, task, MODES[mode], forecast, NOW)
        st = cluster.nodes[d.node]
        t_s, e_kwh = joint_time_energy(st.avg_time_ms / 1000.0,
                                       st.power_w(cluster.host_power_w),
                                       d.remote_frac, d.comm_s)
        lo_i, hi_i = forecast.intensity_interval_batch([d.node], NOW)
        lo_g, hi_g = float(lo_i[0]) * e_kwh, float(hi_i[0]) * e_kwh
        split = (f"layers [0:{d.cut}) local + [{d.cut}:L) remote"
                 if d.cut else "full offload")
        print(f"  {mode:12s} -> {d.node:12s} cut {d.cut:3d} ({split}), "
              f"{d.remote_frac:.0%} remote, uplink {d.comm_s * 1e3:.1f} ms")
        print(f"  {'':12s}    est {t_s * 1e3:.0f} ms, carbon "
              f"{lo_g * 1e3:.3f} .. {hi_g * 1e3:.3f} mgCO2 (90% band)")
    print()

# -- end-to-end: the engine executes and bills only the offloaded segment --
from repro.core.api import CarbonEdgeEngine  # noqa: E402

policy = PartitionPolicy(profiles[0], backend="numpy")
eng = CarbonEdgeEngine(cluster, mode="green", policy=policy,
                       provider=forecast)
res = eng.submit_many([task] * 8).step(now_hour=NOW)
d = policy.last_decisions[0]
print(f"engine.step: {len(res)} tasks on {d.node}, billed "
      f"{res[0].latency_ms:.0f} ms each (offloaded segment of "
      f"{task.base_latency_ms:.0f} ms base); fleet total "
      f"{eng.monitor.total_carbon_g() * 1e3:.3f} mgCO2")
