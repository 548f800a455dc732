"""Carbon Monitor (paper §III.B).

Tracks energy (Eq. 1: E = ∫ P dt, discretised) and emissions
(Eq. 2: C = E * I * PUE) per node/region, with two power sources:

- ``record_power_sample``: wall-clock x sampled power (the CodeCarbon path;
  on this host we sample a process-CPU proxy),
- ``record_step``: workload-derived — roofline step time x device power
  from the compiled artifact (core/energy.py), which lets the scheduler
  score *before* executing (DESIGN.md §4).
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence

import numpy as np

from repro.core import energy as energy_mod
from repro.core.energy import RooflineTerms
from repro.core.providers import intensity_batch

RAM_W_PER_GB = 0.375  # paper §III.B.1 DDR4 approximation


@dataclass
class EnergySample:
    t_s: float
    power_w: float


@dataclass
class RegionAccount:
    intensity_g_per_kwh: float
    pue: float = 1.0
    energy_kwh: float = 0.0
    carbon_g: float = 0.0
    tasks: int = 0
    # True when the intensity was pinned explicitly at register_region time;
    # pinned regions are never overridden by the monitor's provider.
    pinned: bool = False


class CarbonMonitor:
    """Per-region energy/emissions ledger.

    Grid intensity is read through a ``CarbonIntensityProvider``
    (core/api.py) when one is given — time-varying billing via the ``hour``
    argument — otherwise through the static value captured at
    ``register_region`` time (equivalent to a StaticProvider snapshot).
    """

    def __init__(self, provider=None):
        self.provider = provider
        self.regions: Dict[str, RegionAccount] = {}
        self._samples: List[EnergySample] = []

    def register_region(self, name: str, intensity: Optional[float] = None,
                        pue: float = 1.0):
        pinned = intensity is not None
        if intensity is None:
            if self.provider is None:
                raise ValueError(
                    f"register_region({name!r}) needs an intensity or a "
                    "CarbonIntensityProvider")
            intensity = self.provider.intensity(name)
        self.regions[name] = RegionAccount(intensity, pue, pinned=pinned)

    # -- Eq. 1: discretised power integration ------------------------------
    def record_power_sample(self, region: str, dt_s: float, p_gpu_w: float = 0.0,
                            p_cpu_w: float = 0.0, ram_gb: float = 0.0,
                            hour: float = 0.0) -> float:
        p = p_gpu_w + p_cpu_w + ram_gb * RAM_W_PER_GB
        e_kwh = p * dt_s / 3.6e6
        self._samples.append(EnergySample(dt_s, p))
        return self._bill(region, e_kwh, hour)

    # -- workload-derived (roofline) ---------------------------------------
    def record_step(self, region: str, terms: RooflineTerms, chips: int,
                    chip_power_w: float = energy_mod.CHIP_POWER_W,
                    hour: float = 0.0) -> float:
        e_kwh = energy_mod.step_energy_kwh(terms, chips, chip_power_w)
        return self._bill(region, e_kwh, hour)

    # -- pre-computed energy (engine path) ---------------------------------
    def record_energy(self, region: str, e_kwh: float,
                      hour: float = 0.0) -> float:
        return self._bill(region, e_kwh, hour)

    def billing_intensity(self, region: str, hour: float = 0.0) -> float:
        """The intensity a `_bill` at ``hour`` would use — side-effect-free,
        so callers can probe billing inputs before committing work."""
        acc = self.regions[region]
        if self.provider is not None and not acc.pinned:
            return self.provider.intensity(region, hour)
        return acc.intensity_g_per_kwh

    def billing_intensity_batch(self, regions: Sequence[str],
                                hour: float = 0.0) -> np.ndarray:
        """(len(regions),) billing intensities at ``hour`` — the batched,
        side-effect-free form of :meth:`billing_intensity` (DESIGN.md §6).
        Provider-driven (non-pinned) regions are resolved through one
        ``providers.intensity_batch`` call instead of a per-region Python loop;
        pinned or provider-less regions read their registered value. An
        unregistered region raises ``KeyError`` like the scalar probe."""
        accs = [self.regions[r] for r in regions]     # KeyError like scalar
        out = np.array([a.intensity_g_per_kwh for a in accs], dtype=float)
        if self.provider is not None:
            live = [i for i, a in enumerate(accs) if not a.pinned]
            if live:
                vals = intensity_batch(self.provider,
                                       [regions[i] for i in live], hour)
                out[live] = np.asarray(vals, dtype=float)
        return out

    def record_energy_batch(self, regions: Sequence[str], e_kwh,
                            hour: float = 0.0, intensities=None,
                            groups=None) -> np.ndarray:
        """Bill B pre-computed task energies in one shot (DESIGN.md §6):
        the batched form of B :meth:`record_energy` calls.

        ``regions`` is the per-task billing region, ``e_kwh`` a scalar or
        (B,) array. ``intensities`` (scalar or (B,) array) supplies
        pre-resolved billing intensities — the engine passes the values it
        probed before executing, so the billed signal is exactly the probed
        one; ``None`` resolves them here via
        :meth:`billing_intensity_batch`. Carbon is one array-valued
        ``energy.carbon_g`` evaluation, and each region's account is
        updated once, with float accumulations folded in strict task order
        (``energy.ledger_add``) — bit-identical to the per-task loop, in
        O(distinct regions) Python work. Returns the (B,) per-task carbon.

        ``groups`` mirrors ``EdgeCluster.execute_batch``: a precomputed
        ``np.unique(..., return_inverse=True)`` over ``regions``.

        Atomic: all inputs resolve before the first account write."""
        B = len(regions)
        if not B:
            return np.zeros(0)
        e = np.broadcast_to(np.asarray(e_kwh, dtype=float), (B,))
        if groups is None:
            groups = np.unique(np.asarray(regions, dtype=object),
                               return_inverse=True)
        uniq, inverse = groups
        if intensities is None:
            per_uniq = self.billing_intensity_batch(list(uniq), hour)
            ints = per_uniq[inverse]
        else:
            ints = np.broadcast_to(np.asarray(intensities, dtype=float), (B,))
        accs = [self.regions[r] for r in uniq]        # KeyError like scalar
        pues = np.array([a.pue for a in accs], dtype=float)[inverse]
        c = energy_mod.carbon_g(e, ints, pues)
        order = np.argsort(inverse, kind="stable")
        bounds = np.searchsorted(inverse[order], np.arange(len(uniq) + 1))
        for k, acc in enumerate(accs):
            idx = order[bounds[k]:bounds[k + 1]]
            acc.energy_kwh = energy_mod.ledger_add(acc.energy_kwh, e[idx])
            acc.carbon_g = energy_mod.ledger_add(acc.carbon_g, c[idx])
            acc.tasks += int(idx.size)
        return c

    def _bill(self, region: str, e_kwh: float, hour: float = 0.0) -> float:
        acc = self.regions[region]
        c = energy_mod.carbon_g(e_kwh, self.billing_intensity(region, hour),
                                acc.pue)
        acc.energy_kwh += e_kwh
        acc.carbon_g += c
        acc.tasks += 1
        return c

    # -- reporting ----------------------------------------------------------
    def total_carbon_g(self) -> float:
        return sum(a.carbon_g for a in self.regions.values())

    def total_energy_kwh(self) -> float:
        return sum(a.energy_kwh for a in self.regions.values())

    def _effective_intensity(self, acc: RegionAccount) -> float:
        """What the region was actually billed at: the energy-weighted mean
        for provider-driven (possibly time-varying) regions with billed
        energy, else the registration-time value."""
        if self.provider is not None and not acc.pinned and acc.energy_kwh:
            return acc.carbon_g / (acc.energy_kwh * acc.pue)
        return acc.intensity_g_per_kwh

    def report(self) -> Dict[str, Dict[str, float]]:
        return {r: {"energy_kwh": a.energy_kwh, "carbon_g": a.carbon_g,
                    "tasks": a.tasks,
                    "intensity": self._effective_intensity(a)}
                for r, a in self.regions.items()}


class WallClockEnergyTracker:
    """Minimal CodeCarbon-style context: samples process time x power."""

    def __init__(self, monitor: CarbonMonitor, region: str, power_w: float):
        self.monitor, self.region, self.power_w = monitor, region, power_w
        self.elapsed_s = 0.0
        self.carbon_g = 0.0

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.elapsed_s = time.perf_counter() - self._t0
        self.carbon_g = self.monitor.record_power_sample(
            self.region, self.elapsed_s, p_cpu_w=self.power_w)
        return False
