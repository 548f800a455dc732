"""Blackout-tolerant carbon provider with staleness-widened intervals.

:class:`ResilientProvider` wraps any :class:`~repro.core.providers.
CarbonIntensityProvider` (typically the engine's *outermost* one, so a
``ForecastProvider``'s conformal band rides along). Healthy, every read
delegates bit-identically — the DESIGN.md §10 zero-fault contract — while
a **last-known-good (LKG) cache** records each scalar-hour read. During a
blackout window (``begin_blackout``/``end_blackout``, toggled by the
:class:`~repro.resilience.FaultInjector` on ``PROVIDER_OUTAGE`` events) or
when the base provider itself raises, reads degrade to LKG persistence
values, and ``intensity_interval_batch`` *widens* its band by
``widen_g_per_hour × staleness`` — so every conformal consumer
(``plan_wake_risk``, the tenancy risk-deferral gate, DESIGN.md §8/§7)
automatically prices in how stale the grid signal is, with the lower
band clipped at zero.
"""
from __future__ import annotations

from typing import Dict, Sequence

import numpy as np

from repro.core.providers import intensity_batch, intensity_interval_batch


class ResilientProvider:
    """Last-known-good degraded mode over a base provider."""

    def __init__(self, base, widen_g_per_hour: float = 25.0):
        self.base = base
        self.widen_g_per_hour = float(widen_g_per_hour)
        self._outages = 0               # nested blackout windows
        self._lkg: Dict[str, float] = {}
        self._lkg_hour = None           # hour of the newest good read
        self.served_stale = 0           # degraded reads (diagnostics)

    # Unknown attributes (``conformal``, ``window``, ...) delegate to the
    # base so the wrapper is drop-in for planners and the obs layer.
    def __getattr__(self, name):
        return getattr(self.base, name)

    @property
    def TIME_INVARIANT(self) -> bool:  # noqa: N802 (provider protocol attr)
        return getattr(self.base, "TIME_INVARIANT", False)

    @property
    def blackout(self) -> bool:
        return self._outages > 0

    def begin_blackout(self) -> None:
        self._outages += 1

    def end_blackout(self) -> None:
        self._outages = max(0, self._outages - 1)

    def staleness_hours(self, now_hour: float) -> float:
        """How old the LKG snapshot is at ``now_hour`` (0 while healthy)."""
        if not self.blackout or self._lkg_hour is None:
            return 0.0
        return max(0.0, float(now_hour) - self._lkg_hour)

    # -- LKG bookkeeping ---------------------------------------------------
    def _record(self, names: Sequence[str], hour: float, vals) -> None:
        # Scalar-hour reads only (the engine/featcache hot path reads the
        # current hour; array-hour planning reads look into the future and
        # must not advance the snapshot). Keep the newest hour seen.
        if self._lkg_hour is None or hour >= self._lkg_hour:
            self._lkg.update(zip(names, np.atleast_1d(
                np.asarray(vals, dtype=float)).tolist()))
            self._lkg_hour = float(hour)

    def _stale_values(self, names: Sequence[str]) -> np.ndarray:
        vals = np.empty(len(names))
        for j, n in enumerate(names):
            v = self._lkg.get(n)
            if v is None:
                raise KeyError(
                    f"provider blackout and no last-known-good intensity "
                    f"for {n!r}")
            vals[j] = v
        self.served_stale += len(names)
        return vals

    # -- provider protocol -------------------------------------------------
    def intensity(self, node: str, hour: float = 0.0) -> float:
        if not self.blackout:
            try:
                v = self.base.intensity(node, hour)
            except KeyError:
                if node not in self._lkg:
                    raise
                return float(self._stale_values([node])[0])
            self._record([node], float(hour), v)
            return v
        return float(self._stale_values([node])[0])

    def intensity_batch(self, names: Sequence[str], hours) -> np.ndarray:
        h = np.asarray(hours, dtype=float)
        if not self.blackout:
            try:
                vals = np.asarray(intensity_batch(self.base, names, hours))
            except KeyError:
                if not all(n in self._lkg for n in names):
                    raise
                vals = self._stale_values(names)
                return (vals if h.ndim == 0
                        else np.broadcast_to(vals, (h.size, len(names))
                                             ).copy())
            if h.ndim == 0:
                self._record(names, float(h), vals)
            return vals
        vals = self._stale_values(names)
        if h.ndim == 0:
            return vals
        # persistence: the stale snapshot answers every queried hour
        return np.broadcast_to(vals, (h.size, len(names))).copy()

    def intensity_interval_batch(self, names: Sequence[str], hours,
                                 coverage: float = 0.9):
        if not self.blackout:
            # healthy: the base's own band, bit-identical
            return intensity_interval_batch(self.base, names, hours,
                                            coverage=coverage)
        h = np.asarray(hours, dtype=float)
        pred = self.intensity_batch(names, hours)
        # base conformal quantile (if calibrated) + staleness widening:
        # queried hours further from the LKG snapshot get wider bands
        q0 = 0.0
        conf = getattr(self.base, "conformal", None)
        if conf is not None:
            q0 = float(conf.quantile(coverage))
        anchor = self._lkg_hour if self._lkg_hour is not None else 0.0
        stale = np.maximum(0.0, h - anchor)
        q = q0 + self.widen_g_per_hour * stale
        if h.ndim != 0:
            q = q[:, None]                          # (S, 1) over (S, N)
        return np.maximum(pred - q, 0.0), pred + q

    def covers(self, node: str) -> bool:
        if self.blackout:
            return node in self._lkg
        cov = getattr(self.base, "covers", None)
        return bool(cov(node)) if cov is not None else True
