"""Split-conformal uncertainty for the scheduler's two noisy signals
(DESIGN.md §8): forecast grid-carbon intensity and the latency model.

CarbonCP's observation (PAPERS.md) is that carbon-aware partition and
deferral decisions made on *point* forecasts silently gamble: a deferral
into a mispredicted "green" window loses carbon. Split-conformal
prediction fixes the decision rule, not the forecast — calibrate the
absolute residuals of a held-out window, and the quantile

    q = the ceil((n + 1) * coverage)-th smallest |residual|

gives a symmetric band ``pred ± q`` with finite-sample marginal coverage
>= ``coverage`` under exchangeability (the standard split-conformal
guarantee, no distributional assumptions). Risk-bounded callers
(``core.temporal.plan_wake_risk_batch``, the tenancy deferral gate) then
defer/reject only when the *pessimistic* end of the band still beats
executing now.

The provider-facing plumbing lives in ``core.providers`` (the
``intensity_interval_batch`` dispatch helper plus native zero-width
intervals on the measured providers); this module owns the calibrators.
"""
from __future__ import annotations

from typing import Sequence

import numpy as np

from repro.core.providers import (CarbonIntensityProvider, intensity_batch,
                                  intensity_interval_batch)

__all__ = [
    "SplitConformal", "ConformalProvider", "calibrate_intensity",
    "calibrate_latency", "intensity_interval_batch",
]


class SplitConformal:
    """Split-conformal calibrator over absolute residuals.

    ``residuals`` is any array of held-out ``actual - predicted`` values
    (signs are discarded). ``quantile(coverage)`` returns the
    finite-sample-corrected order statistic — ``inf`` when the calibration
    set is too small to certify the requested coverage (n + 1 <= n *
    coverage), which callers should read as "no risk bound available".
    """

    def __init__(self, residuals):
        r = np.sort(np.abs(np.asarray(residuals, dtype=float).ravel()))
        if r.size == 0:
            raise ValueError("SplitConformal needs at least one residual")
        self._r = r

    @property
    def n(self) -> int:
        return int(self._r.size)

    def quantile(self, coverage: float = 0.9) -> float:
        if not 0.0 < coverage < 1.0:
            raise ValueError(f"coverage must be in (0, 1), got {coverage}")
        k = int(np.ceil((self._r.size + 1) * coverage))
        if k > self._r.size:
            return float("inf")
        return float(self._r[k - 1])

    def interval(self, pred, coverage: float = 0.9):
        """``pred ± quantile(coverage)`` elementwise (scalars or arrays)."""
        q = self.quantile(coverage)
        p = np.asarray(pred, dtype=float)
        return p - q, p + q


class ConformalProvider:
    """Wrap any intensity provider with a :class:`SplitConformal` band.

    Point reads pass through untouched (the engine's billing path is
    unchanged); ``intensity_interval_batch`` answers ``pred ± q`` with the
    lower band clipped at zero. Use this to retrofit intervals onto a
    provider that has none, or to override a bundled provider's native
    (zero-width) answer with an empirically calibrated one.
    """

    def __init__(self, base: CarbonIntensityProvider,
                 conformal: SplitConformal):
        self.base = base
        self.conformal = conformal

    @property
    def TIME_INVARIANT(self) -> bool:          # noqa: N802 (provider protocol)
        return bool(getattr(self.base, "TIME_INVARIANT", False))

    def intensity(self, node: str, hour: float = 0.0) -> float:
        return self.base.intensity(node, hour)

    def intensity_batch(self, names: Sequence[str], hours) -> np.ndarray:
        return np.asarray(intensity_batch(self.base, names, hours))

    def covers(self, node: str) -> bool:
        cov = getattr(self.base, "covers", None)
        return bool(cov(node)) if cov is not None else True

    def intensity_interval_batch(self, names: Sequence[str], hours,
                                 coverage: float = 0.9):
        pred = np.asarray(self.intensity_batch(names, hours), dtype=float)
        q = self.conformal.quantile(coverage)
        return np.maximum(pred - q, 0.0), pred + q


def calibrate_intensity(forecast: CarbonIntensityProvider,
                        actual: CarbonIntensityProvider,
                        names: Sequence[str], hours) -> SplitConformal:
    """Calibrate forecast-vs-actual intensity residuals over a held-out
    (names x hours) calibration window — one batched read per provider.
    Attach the result to a ``ForecastProvider(conformal=...)`` or wrap the
    forecast in a :class:`ConformalProvider`."""
    pred = np.asarray(intensity_batch(forecast, names, hours), dtype=float)
    true = np.asarray(intensity_batch(actual, names, hours), dtype=float)
    return SplitConformal(true - pred)


def calibrate_latency(predicted_ms, measured_ms) -> SplitConformal:
    """Calibrate the latency model's residuals (predicted vs measured
    service time, e.g. ``cluster.latency_energy`` estimates against
    ``TaskResult.latency_ms``). The returned calibrator's ``interval``
    bounds future latency predictions for risk-bounded admission."""
    p = np.asarray(predicted_ms, dtype=float).ravel()
    m = np.asarray(measured_ms, dtype=float).ravel()
    if p.size != m.size:
        raise ValueError(
            f"predicted/measured length mismatch: {p.size} vs {m.size}")
    return SplitConformal(m - p)
