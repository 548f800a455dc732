"""Carbon-intensity providers (DESIGN.md §1.1): the one way schedulers,
routers and the CarbonMonitor read grid intensity.

:class:`CarbonIntensityProvider` is the protocol; :func:`intensity_batch`
and :func:`intensity_interval_batch` are the batched reads every caller
goes through. :class:`StaticProvider` wraps the per-node regional
constants (paper §IV.A static scenario), :class:`TraceProvider` wraps
diurnal :class:`~repro.core.temporal.IntensityTrace` signals (or a
regional CSV via :func:`load_intensity_csv`), :class:`FallbackProvider`
covers a partial feed with another provider, and
:class:`ForecastProvider` composes over any base provider (persistence
lead + smoothing, optionally a conformal band).

This layer sits under the feature cache, the monitor, the policies and
the engine: it imports none of them. The trace types it builds come from
:mod:`repro.core.temporal`, which imports the policies, so those imports
stay inside the functions that need them.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import (TYPE_CHECKING, Dict, List, Mapping, Optional, Protocol,
                    Sequence, runtime_checkable)

import numpy as np

if TYPE_CHECKING:
    from repro.core.cluster import EdgeCluster



@runtime_checkable
class CarbonIntensityProvider(Protocol):
    """Single source of grid carbon intensity (gCO2/kWh) per node/region.

    Providers *may* additionally implement the batched form
    ``intensity_batch(names, hours)`` (see :func:`intensity_batch` for the
    contract); callers go through the module-level helper, which falls back
    to per-name ``intensity`` calls for providers that don't.
    """

    def intensity(self, node: str, hour: float = 0.0) -> float:
        ...


def intensity_batch(provider: CarbonIntensityProvider,
                    names: Sequence[str], hours) -> np.ndarray:
    """Batched provider read — the fleet-scale hot path (DESIGN.md §3).

    ``hours`` is a scalar or an (S,) array; returns ``(N,)`` respectively
    ``(S, N)`` gCO2/kWh for the N ``names``. Dispatches to the provider's
    vectorized ``intensity_batch`` when it has one (all bundled providers
    do); any custom provider is served by a per-name/per-hour fallback loop
    with identical semantics — including raising ``KeyError`` for uncovered
    nodes, so partial-coverage masking stays with the caller.
    """
    fn = getattr(provider, "intensity_batch", None)
    if fn is not None:
        return fn(names, hours)
    h = np.asarray(hours, dtype=float)
    if h.ndim == 0:
        return np.array([provider.intensity(n, float(h)) for n in names])
    return np.array([[provider.intensity(n, float(t)) for n in names]
                     for t in h])


def intensity_interval_batch(provider: CarbonIntensityProvider,
                             names: Sequence[str], hours,
                             coverage: float = 0.9):
    """Batched ``(lo, hi)`` conformal intensity interval read (DESIGN.md
    §8): each array shaped like :func:`intensity_batch`'s result.

    Dispatches to the provider's ``intensity_interval_batch`` when it has
    one (all bundled providers do — measured signals answer zero-width
    intervals, a calibrated :class:`ForecastProvider` answers its
    split-conformal band); any other provider degrades to the degenerate
    point interval ``lo == hi == intensity_batch(...)``, which keeps every
    risk-bounded caller exact-but-risk-blind rather than failing.
    """
    fn = getattr(provider, "intensity_interval_batch", None)
    if fn is not None:
        return fn(names, hours, coverage=coverage)
    v = np.asarray(intensity_batch(provider, names, hours), dtype=float)
    return v, v.copy()


def _point_interval(vals):
    v = np.asarray(vals, dtype=float)
    return v, v.copy()


@dataclass(frozen=True)
class StaticProvider:
    """Time-invariant regional intensities (paper §IV.A scenario)."""

    table: Mapping[str, float]
    default: Optional[float] = None

    # Hour-independent: the FeatureCache may reuse answers across steps.
    TIME_INVARIANT = True

    def intensity(self, node: str, hour: float = 0.0) -> float:
        v = self.table.get(node, self.default)
        if v is None:
            raise KeyError(f"no carbon intensity registered for {node!r}")
        return v

    def intensity_batch(self, names: Sequence[str], hours) -> np.ndarray:
        vals = np.array([self.intensity(n) for n in names], dtype=float)
        h = np.asarray(hours, dtype=float)
        if h.ndim == 0:
            return vals
        return np.broadcast_to(vals, (h.size, len(names))).copy()

    def intensity_interval_batch(self, names: Sequence[str], hours,
                                 coverage: float = 0.9):
        # Registered constants are exact: zero-width interval.
        return _point_interval(self.intensity_batch(names, hours))

    def covers(self, node: str) -> bool:
        return self.default is not None or node in self.table

    @classmethod
    def from_cluster(cls, cluster: EdgeCluster) -> "StaticProvider":
        return cls({name: st.spec.carbon_intensity
                    for name, st in cluster.nodes.items()})

    @classmethod
    def from_pods(cls, pods: Sequence) -> "StaticProvider":
        return cls({p.name: p.carbon_intensity for p in pods})


@dataclass(frozen=True)
class TraceProvider:
    """Diurnal per-node traces (anything with ``.at(hour)``), falling back
    to another provider for nodes without a trace."""

    traces: Mapping[str, object]          # node -> IntensityTrace-like
    fallback: Optional[CarbonIntensityProvider] = None

    def intensity(self, node: str, hour: float = 0.0) -> float:
        tr = self.traces.get(node)
        if tr is not None:
            return tr.at(hour)
        if self.fallback is not None:
            return self.fallback.intensity(node, hour)
        raise KeyError(f"no trace or fallback intensity for {node!r}")

    def intensity_batch(self, names: Sequence[str], hours) -> np.ndarray:
        from repro.core.temporal import IntensityTrace

        h = np.asarray(hours, dtype=float)
        hs = h.reshape(-1)
        out = np.empty((hs.size, len(names)))
        missing = []
        rows, row_cols = [], []
        for j, n in enumerate(names):
            tr = self.traces.get(n)
            if tr is None:
                missing.append(j)
                continue
            # Joint interpolation only for genuine IntensityTrace semantics
            # (a user trace with a .values table but its own .at must keep
            # its own sampling — batch must stay bit-identical to scalar).
            if type(tr).at is IntensityTrace.at:
                rows.append(tr.values)     # hourly table: joint interpolation
                row_cols.append(j)
            else:
                # a user-supplied trace type: sample through its .at —
                # array-aware when it accepts arrays, per hour otherwise
                try:
                    out[:, j] = tr.at(hs)
                except (TypeError, ValueError):
                    out[:, j] = [tr.at(float(t)) for t in hs]
        if rows:
            # one joint interpolation over all (name, hour) pairs, through
            # the same arithmetic IntensityTrace.at evaluates
            from repro.core.temporal import interp_hourly

            V = np.asarray(rows, dtype=float)              # (M, 24)
            out[:, row_cols] = interp_hourly(V, hs).T      # (M, S) -> (S, M)
        if missing:
            if self.fallback is None:
                raise KeyError(
                    f"no trace or fallback intensity for {names[missing[0]]!r}")
            sub = intensity_batch(self.fallback,
                                  [names[j] for j in missing], hs)
            out[:, missing] = np.asarray(sub).reshape(hs.size, len(missing))
        return out[0] if h.ndim == 0 else out

    def intensity_interval_batch(self, names: Sequence[str], hours,
                                 coverage: float = 0.9):
        # Traces are the measured ground-truth signal: zero-width for
        # traced nodes; untraced nodes get the fallback's intervals.
        h = np.asarray(hours, dtype=float)
        hs = h.reshape(-1)
        lo = np.empty((hs.size, len(names)))
        hi = np.empty((hs.size, len(names)))
        have = [j for j, n in enumerate(names) if n in self.traces]
        miss = [j for j in range(len(names)) if j not in set(have)]
        if have:
            v = np.asarray(self.intensity_batch([names[j] for j in have],
                                                hs)).reshape(hs.size,
                                                             len(have))
            lo[:, have] = v
            hi[:, have] = v
        if miss:
            if self.fallback is None:
                raise KeyError(
                    f"no trace or fallback intensity for {names[miss[0]]!r}")
            sub_lo, sub_hi = intensity_interval_batch(
                self.fallback, [names[j] for j in miss], hs,
                coverage=coverage)
            lo[:, miss] = np.asarray(sub_lo).reshape(hs.size, len(miss))
            hi[:, miss] = np.asarray(sub_hi).reshape(hs.size, len(miss))
        return (lo[0], hi[0]) if h.ndim == 0 else (lo, hi)

    def covers(self, node: str) -> bool:
        if node in self.traces:
            return True
        cov = getattr(self.fallback, "covers", None)
        return bool(cov(node)) if cov is not None else self.fallback is not None

    @classmethod
    def from_csv(cls, source: str, *,
                 node_zones: Optional[Mapping[str, str]] = None,
                 fallback: Optional[CarbonIntensityProvider] = None,
                 zone_column: Optional[str] = None,
                 value_column: Optional[str] = None,
                 time_column: Optional[str] = None) -> "TraceProvider":
        """Build a provider from an ElectricityMaps-style regional CSV.

        ``node_zones`` maps node names onto CSV zones so a fleet can share
        a handful of regional feeds; omitted, the zones themselves are the
        keys (nodes named after their zone resolve directly).
        """
        zones = load_intensity_csv(source, zone_column=zone_column,
                                   value_column=value_column,
                                   time_column=time_column)
        if node_zones is None:
            traces: Dict[str, object] = dict(zones)
        else:
            traces = {}
            for node, zone in node_zones.items():
                if zone not in zones:
                    raise KeyError(
                        f"zone {zone!r} for node {node!r} not in CSV "
                        f"(zones: {sorted(zones)})")
                traces[node] = zones[zone]
        return cls(traces=traces, fallback=fallback)


_CSV_TIME_COLS = ("datetime", "timestamp", "hour", "time")
_CSV_ZONE_COLS = ("zone", "zone_name", "zone_key", "zone_id", "region")


def _csv_hour(text: str) -> float:
    """A CSV timestamp as simulator hours: numeric hours pass through;
    ISO datetimes become hours elapsed since midnight of the first day
    (callers subtract a common base, so only differences matter)."""
    try:
        return float(text)
    except ValueError:
        pass
    from datetime import datetime, timezone

    dt = datetime.fromisoformat(text.strip().replace("Z", "+00:00"))
    if dt.tzinfo is None:
        dt = dt.replace(tzinfo=timezone.utc)
    return dt.timestamp() / 3600.0


def load_intensity_csv(source: str, *,
                       zone_column: Optional[str] = None,
                       value_column: Optional[str] = None,
                       time_column: Optional[str] = None) -> Dict[str, object]:
    """Parse a regional carbon-intensity CSV (ElectricityMaps export
    shape: one row per (timestamp, zone)) into per-zone
    :class:`~repro.core.temporal.SeriesTrace` signals.

    ``source`` is a path, or the CSV text itself when it contains a
    newline. Columns are auto-detected unless named explicitly: time from
    ``datetime``/``timestamp``/``hour``/``time``, zone from ``zone``/
    ``zone_name``/``zone_key``/``zone_id``/``region`` (a single-zone CSV
    may omit it — zone ``""``), value from the first header mentioning
    ``carbon_intensity`` then ``intensity``. Rows per zone are sorted by
    time and must be uniformly spaced; ISO datetimes are rebased so the
    earliest stamp in the file is hour-of-day of that stamp (a midnight-
    started day trace lands on hours 0..23, matching ``IntensityTrace``).
    """
    import csv
    import io

    from repro.core.temporal import SeriesTrace

    if "\n" in source:
        fh = io.StringIO(source)
    else:
        fh = open(source, newline="")
    try:
        reader = csv.DictReader(fh)
        headers = [h.strip() for h in (reader.fieldnames or [])]
        low = {h.lower(): h for h in headers}

        def pick(explicit, candidates, what, required=True):
            if explicit is not None:
                if explicit not in headers:
                    raise KeyError(f"{what} column {explicit!r} not in CSV "
                                   f"header {headers}")
                return explicit
            for c in candidates:
                if c in low:
                    return low[c]
            if required:
                raise KeyError(f"no {what} column found in CSV header "
                               f"{headers}")
            return None

        tcol = pick(time_column, _CSV_TIME_COLS, "time")
        zcol = pick(zone_column, _CSV_ZONE_COLS, "zone", required=False)
        if value_column is not None:
            vcol = pick(value_column, (), "value")
        else:
            vcol = next((h for h in headers
                         if "carbon_intensity" in h.lower()),
                        None) or next((h for h in headers
                                       if "intensity" in h.lower()), None)
            if vcol is None:
                raise KeyError(
                    f"no carbon-intensity column found in CSV header "
                    f"{headers}")

        rows: Dict[str, List[tuple]] = {}
        iso_seen = False
        for rec in reader:
            t_text = (rec.get(tcol) or "").strip()
            v_text = (rec.get(vcol) or "").strip()
            if not t_text or not v_text:
                continue      # ElectricityMaps exports gap rows as blanks
            try:
                float(t_text)
            except ValueError:
                iso_seen = True
            zone = (rec.get(zcol) or "").strip() if zcol else ""
            rows.setdefault(zone, []).append((_csv_hour(t_text),
                                              float(v_text)))
        if not rows:
            raise ValueError("CSV contains no intensity rows")

        if iso_seen:
            # Rebase absolute epoch-hours so the file's earliest stamp
            # keeps its hour-of-day and everything else is relative to it.
            t0 = min(t for series in rows.values() for t, _ in series)
            base = t0 - (t0 % 24.0)
            rows = {z: [(t - base, v) for t, v in series]
                    for z, series in rows.items()}

        out: Dict[str, object] = {}
        for zone, series in rows.items():
            series.sort(key=lambda tv: tv[0])
            hours = [t for t, _ in series]
            values = [v for _, v in series]
            if len(hours) > 1:
                steps = np.diff(np.asarray(hours, dtype=float))
                step = float(steps[0])
                if step <= 0 or not np.allclose(steps, step, rtol=1e-6,
                                                atol=1e-9):
                    raise ValueError(
                        f"zone {zone!r}: rows are not uniformly spaced "
                        f"in time (steps {sorted(set(steps.tolist()))[:4]})")
            else:
                step = 1.0
            out[zone] = SeriesTrace(region=zone, values=tuple(values),
                                    start_hour=float(hours[0]),
                                    step_hours=step)
        return out
    finally:
        fh.close()


@dataclass(frozen=True)
class FallbackProvider:
    """Try ``primary``, fall back to ``fallback`` for uncovered nodes —
    e.g. a partial trace feed over the fleet's static regional values."""

    primary: CarbonIntensityProvider
    fallback: CarbonIntensityProvider

    def intensity(self, node: str, hour: float = 0.0) -> float:
        try:
            return self.primary.intensity(node, hour)
        except KeyError:
            return self.fallback.intensity(node, hour)

    def intensity_batch(self, names: Sequence[str], hours) -> np.ndarray:
        # Fast split when the primary can report coverage (all bundled
        # providers can): two batched calls, no per-name machinery.
        cov = getattr(self.primary, "covers", None)
        if cov is not None:
            try:
                covered = [j for j, n in enumerate(names) if cov(n)]
                if len(covered) == len(names):
                    return np.asarray(intensity_batch(self.primary, names,
                                                      hours))
                h = np.asarray(hours, dtype=float)
                hs = h.reshape(-1)
                out = np.empty((hs.size, len(names)))
                uncovered = [j for j in range(len(names))
                             if j not in set(covered)]
                if covered:
                    sub = intensity_batch(self.primary,
                                          [names[j] for j in covered], hs)
                    out[:, covered] = np.asarray(sub).reshape(hs.size,
                                                              len(covered))
                sub = intensity_batch(self.fallback,
                                      [names[j] for j in uncovered], hs)
                out[:, uncovered] = np.asarray(sub).reshape(hs.size,
                                                            len(uncovered))
                return out[0] if h.ndim == 0 else out
            except KeyError:
                pass      # optimistic covers(): degrade to per-name below
        else:
            try:
                return np.asarray(intensity_batch(self.primary, names,
                                                  hours))
            except KeyError:
                pass
        # Coverage-opaque primary: resolve per name (each name still
        # batched over all hours).
        h = np.asarray(hours, dtype=float)
        hs = h.reshape(-1)
        cols = []
        for n in names:
            try:
                col = intensity_batch(self.primary, [n], hs)
            except KeyError:
                col = intensity_batch(self.fallback, [n], hs)
            cols.append(np.asarray(col).reshape(hs.size))
        out = np.stack(cols, axis=1)
        return out[0] if h.ndim == 0 else out

    def intensity_interval_batch(self, names: Sequence[str], hours,
                                 coverage: float = 0.9):
        # Planning-path read (not per-step hot): resolve per name so each
        # node gets ITS provider's interval — primary when covered,
        # fallback otherwise — with the same KeyError-degradation rule as
        # the point read above.
        h = np.asarray(hours, dtype=float)
        hs = h.reshape(-1)
        lo = np.empty((hs.size, len(names)))
        hi = np.empty((hs.size, len(names)))
        cov = getattr(self.primary, "covers", None)
        for j, n in enumerate(names):
            use_primary = bool(cov(n)) if cov is not None else True
            sub = None
            if use_primary:
                try:
                    sub = intensity_interval_batch(self.primary, [n], hs,
                                                   coverage=coverage)
                except KeyError:
                    sub = None
            if sub is None:
                sub = intensity_interval_batch(self.fallback, [n], hs,
                                               coverage=coverage)
            lo[:, j] = np.asarray(sub[0]).reshape(hs.size)
            hi[:, j] = np.asarray(sub[1]).reshape(hs.size)
        return (lo[0], hi[0]) if h.ndim == 0 else (lo, hi)


@dataclass(frozen=True)
class ForecastProvider:
    """Composable forecast view over any base provider.

    ``lead_hours`` shifts the query time (persistence forecast for a
    deferral decision made now about time t+lead); ``smoothing_hours``
    averages the base signal over a centred window, modelling forecast
    uncertainty flattening out short-lived dips.

    ``conformal`` optionally attaches a split-conformal residual
    calibrator (anything with ``quantile(coverage) -> float``, e.g.
    :class:`repro.partition.uncertainty.SplitConformal` built by
    ``calibrate_intensity``): ``intensity_interval_batch`` then answers
    the symmetric conformal band around the forecast instead of a
    zero-width point interval.
    """

    base: CarbonIntensityProvider
    lead_hours: float = 0.0
    smoothing_hours: float = 0.0
    samples: int = 5
    conformal: Optional[object] = None

    def intensity(self, node: str, hour: float = 0.0) -> float:
        t = hour + self.lead_hours
        if self.smoothing_hours <= 0.0:
            return self.base.intensity(node, t)
        half = self.smoothing_hours / 2.0
        ts = np.linspace(t - half, t + half, max(2, self.samples))
        return float(np.mean([self.base.intensity(node, float(x)) for x in ts]))

    def intensity_batch(self, names: Sequence[str], hours) -> np.ndarray:
        h = np.asarray(hours, dtype=float)
        t = h + self.lead_hours
        if self.smoothing_hours <= 0.0:
            return np.asarray(intensity_batch(self.base, names,
                                              t if t.ndim else float(t)))
        half = self.smoothing_hours / 2.0
        # np.linspace over array endpoints evaluates the exact scalar-path
        # sample times per hour; mean over the sample axis matches the
        # scalar np.mean ordering, keeping batch == scalar bit-identical.
        ts = np.linspace(t - half, t + half, max(2, self.samples))  # (K, ...)
        ts2 = ts.reshape(ts.shape[0], -1)                           # (K, S)
        grids = [np.asarray(intensity_batch(self.base, names, ts2[k]))
                 for k in range(ts2.shape[0])]
        out = np.mean(grids, axis=0)                                # (S, N)
        return out[0] if h.ndim == 0 else out

    def intensity_interval_batch(self, names: Sequence[str], hours,
                                 coverage: float = 0.9):
        pred = np.asarray(self.intensity_batch(names, hours), dtype=float)
        if self.conformal is None:
            return pred, pred.copy()
        q = float(self.conformal.quantile(coverage))
        # Intensities are non-negative physical quantities: clip the lower
        # band at zero rather than promising a negative grid.
        return np.maximum(pred - q, 0.0), pred + q

    def window(self, node: str, start_hour: float, end_hour: float,
               step_hours: float = 0.5) -> np.ndarray:
        """Forecast series over [start, end) — used for deferral planning."""
        ts = np.arange(start_hour, end_hour, step_hours)
        return np.array([self.intensity(node, float(t)) for t in ts])
