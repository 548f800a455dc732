"""Quickstart: CarbonEdge's three mechanisms in ~70 lines.

1. schedule with the carbon-aware NSA through the CarbonEdgeEngine
   (paper Eq. 3/4, Table I modes; DESIGN.md policy/provider API);
2. partition a model with the green partitioner (paper Eq. 5);
3. account energy/carbon with the Carbon Monitor (paper Eq. 1/2).

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.configs.cnn_zoo import get_cnn_config
from repro.core.api import CarbonEdgeEngine
from repro.core.carbon import CarbonMonitor
from repro.core.cluster import EdgeCluster, PAPER_NODES
from repro.core.partitioner import green_weights, partition_cnn
from repro.core.providers import StaticProvider
from repro.core.scheduler import MODES, Task, score_table

# -- 1. carbon-aware scheduling (engine facade) ------------------------------
cluster = EdgeCluster(nodes=PAPER_NODES, host_power_w=142.0)
cluster.profile(base_latency_ms=254.85)           # seed per-node history
task = Task(cpu=0.1, mem_mb=64, base_latency_ms=254.85)

print("score components [S_R S_L S_P S_B S_C]:")
for node, s in score_table(cluster, task).items():
    print(f"  {node:12s} {np.round(s, 3)}")

# grid intensity flows through a provider; scheduling through a policy —
# the engine defaults to the batched vectorized/Pallas path.
provider = StaticProvider.from_cluster(cluster)
for mode in MODES:
    engine = CarbonEdgeEngine(
        EdgeCluster(nodes=PAPER_NODES, host_power_w=142.0), mode=mode,
        provider=provider)
    engine.cluster.profile(254.85)
    rep = engine.run(task=task, iterations=10)
    top = max(rep["distribution"], key=rep["distribution"].get)
    print(f"{mode:12s} -> {top}  "
          f"({rep['totals']['carbon_g_per_inf']*1e3:.2f} mgCO2/inf, "
          f"policy={rep['policy']})")

# -- 2. green partitioning ---------------------------------------------------
cfg = get_cnn_config("mobilenetv2")
cpus = [n.cpu for n in PAPER_NODES]
intensities = [n.carbon_intensity for n in PAPER_NODES]
part = partition_cnn(cfg, green_weights(cpus, intensities), comm_weight=1e-9)
print(f"\nmobilenetv2 partitioned into {part.num_segments} segments "
      f"at layer boundaries {part.boundaries}")
print(f"segment costs (Eq.5): {[f'{c:.2e}' for c in part.segment_costs]}")

# -- 3. carbon accounting ----------------------------------------------------
monitor = CarbonMonitor()
monitor.register_region("hydro-rich", intensity=380.0)
carbon = monitor.record_power_sample("hydro-rich", dt_s=0.272, p_cpu_w=142.0)
print(f"\none inference on the green node: {carbon:.5f} gCO2 "
      f"(paper Table II green: 0.0041)")
