"""Green request router: the paper's NSA applied at pod/mesh-slice scale.

Each serving *domain* (a TPU pod or mesh slice in a grid region) is a
NodeSpec; requests are routed with the same Eq. 3 scoring, with E_est
derived from the compiled step's roofline terms instead of wall-clock
history (core/carbon.record_step).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional

from repro.core import energy as energy_mod
from repro.core.carbon import CarbonMonitor
from repro.core.cluster import EdgeCluster, NodeSpec
from repro.core.energy import RooflineTerms
from repro.core.providers import (CarbonIntensityProvider, FallbackProvider,
                                  StaticProvider)
from repro.core.scheduler import MODES, Task


@dataclass(frozen=True)
class PodSpec:
    name: str
    chips: int
    region: str
    carbon_intensity: float
    chip_power_w: float = energy_mod.CHIP_POWER_W


class GreenRouter:
    """Routes inference batches across pods; accounts carbon per region.

    Routing goes through a :class:`~repro.core.api.SchedulingPolicy`
    (default: the vectorized/Pallas path) and an intensity provider — pods'
    static regional values unless a TraceProvider/ForecastProvider is
    injected for time-varying grids.
    """

    def __init__(self, pods: List[PodSpec], mode: str = "green",
                 policy=None,
                 provider: Optional[CarbonIntensityProvider] = None):
        nodes = [
            NodeSpec(p.name, cpu=1.0, mem_mb=1 << 20,
                     carbon_intensity=p.carbon_intensity,
                     power_w=p.chips * p.chip_power_w, region=p.region)
            for p in pods
        ]
        self.pods = {p.name: p for p in pods}
        self.cluster = EdgeCluster(nodes=nodes, host_power_w=0.0)
        self.weights = MODES[mode]
        # An injected provider (e.g. a partial trace feed) falls back to
        # each pod's own static carbon_intensity for uncovered pods.
        static = StaticProvider.from_pods(pods)
        self.provider = (FallbackProvider(provider, static)
                         if provider is not None else static)
        if policy is None:
            from repro.core.policy import VectorizedPolicy
            policy = VectorizedPolicy()
        self.policy = policy
        self.monitor = CarbonMonitor(provider=self.provider)
        for p in pods:
            self.monitor.register_region(p.name)

    def seed_profile(self, step_terms: Dict[str, RooflineTerms]):
        """Seed per-pod history from each pod's compiled roofline step time."""
        for name, terms in step_terms.items():
            self.cluster.nodes[name].avg_time_ms = terms.step_time_s * 1e3

    def route(self, task: Optional[Task] = None, now_hour: float = 0.0) -> str:
        task = task or Task(cpu=0.0, mem_mb=0.0)
        choice = self.policy.select(self.cluster, task, self.weights,
                                    provider=self.provider, now_hour=now_hour)
        if choice is None:
            raise RuntimeError("no feasible pod")
        return choice

    def commit(self, pod_name: str, terms: RooflineTerms,
               hour: float = 0.0) -> float:
        """Account one executed batch on `pod_name`; returns gCO2."""
        pod = self.pods[pod_name]
        c = self.monitor.record_step(pod_name, terms, pod.chips,
                                     pod.chip_power_w, hour=hour)
        st = self.cluster.nodes[pod_name]
        st.completed += 1
        t_ms = terms.step_time_s * 1e3
        # Exponential moving average of history.
        st.avg_time_ms = 0.9 * st.avg_time_ms + 0.1 * t_ms if st.avg_time_ms else t_ms
        return c
