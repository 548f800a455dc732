"""CarbonEdge public API (DESIGN.md §1): providers, policies, engine.

Three abstractions unify what the seed implemented four divergent times:

- :class:`CarbonIntensityProvider` — the *only* way schedulers, routers and
  the CarbonMonitor read grid intensity. :class:`StaticProvider` wraps the
  per-node regional constants (paper §IV.A static scenario),
  :class:`TraceProvider` wraps diurnal :class:`~repro.core.temporal.IntensityTrace`
  signals, and :class:`ForecastProvider` composes over any base provider
  (persistence lead + smoothing — an Electricity Maps-style forecast feed).

- :class:`SchedulingPolicy` (protocol) — one scoring rule (paper Eq. 3/4,
  Algorithm 1), three implementations in :mod:`repro.core.policy`:
  ``WeightedScoringPolicy`` (scalar oracle), ``VectorizedPolicy`` (batched
  numpy / Pallas ``node_score`` kernel — the default), and
  ``TemporalPolicy`` (slot-grid deferral as a time-indexed feature column).

- :class:`CarbonEdgeEngine` — the facade: ``submit``/``step``/``run``/
  ``report``. ``step`` scores B pending tasks against N nodes in a single
  scorer call (one Pallas kernel launch on TPU) instead of one Python loop
  per task.
"""
from __future__ import annotations

import warnings
from dataclasses import dataclass
from typing import Dict, List, Mapping, Optional, Protocol, Sequence, runtime_checkable

import numpy as np

from repro.core.carbon import CarbonMonitor
from repro.core.cluster import EdgeCluster, TaskResult
from repro.core.energy import carbon_g
from repro.core.scheduler import MODES, Task, Weights
from repro.obs.profiler import span


# ---------------------------------------------------------------------------
# Carbon intensity providers
# ---------------------------------------------------------------------------


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


# ---------------------------------------------------------------------------
# Scheduling policy protocol (implementations: repro/core/policy.py)
# ---------------------------------------------------------------------------


@runtime_checkable
class SchedulingPolicy(Protocol):
    """One scoring rule (Eq. 3/4), pluggable execution strategy."""

    name: str

    def select(self, cluster: EdgeCluster, task: Task, weights: Weights,
               provider: Optional[CarbonIntensityProvider] = None,
               now_hour: float = 0.0) -> Optional[str]:
        ...

    def select_batch(self, cluster: EdgeCluster, tasks: Sequence[Task],
                     weights: Weights,
                     provider: Optional[CarbonIntensityProvider] = None,
                     now_hour: float = 0.0) -> List[Optional[str]]:
        ...


# ---------------------------------------------------------------------------
# Engine facade
# ---------------------------------------------------------------------------


class NoFeasibleNodeError(RuntimeError):
    """A task in the batch had no feasible placement.

    ``executed`` holds the TaskResults of batch tasks that completed (and
    were billed) before the failure; the failing task and the unexecuted
    tail are back at the head of the engine queue.
    """

    def __init__(self, executed: List[TaskResult]):
        super().__init__("no feasible node")
        self.executed = executed


class CarbonEdgeEngine:
    """Batched carbon-aware scheduling engine (DESIGN.md §1.3).

    Owns a cluster, a policy, an intensity provider and a CarbonMonitor.
    ``step()`` drains up to ``batch_size`` pending tasks, scoring the whole
    batch against all N nodes in one vectorised/Pallas call, then executes
    placements and bills energy per region through the provider.
    """

    def __init__(self, cluster: EdgeCluster, *, mode: str = "green",
                 weights: Optional[Weights] = None,
                 policy: Optional[SchedulingPolicy] = None,
                 provider: Optional[CarbonIntensityProvider] = None,
                 monitor: Optional[CarbonMonitor] = None,
                 batch_size: Optional[int] = None,
                 batch_execute: bool = True,
                 obs=None, resilience=None, max_requeues: int = 5):
        self.cluster = cluster
        # Batched execute+billing fast path (DESIGN.md §6), on by default;
        # False forces the per-task loop — the bit-exact parity oracle
        # (same pattern as featurize vs featurize_cached).
        self.batch_execute = batch_execute
        self.weights = weights if weights is not None else MODES[mode]
        self.provider = provider or StaticProvider.from_cluster(cluster)
        if policy is None:
            from repro.core.policy import VectorizedPolicy
            policy = VectorizedPolicy()
        self.policy = policy
        # Multi-tenant admission protocol (DESIGN.md §7): a policy exposing
        # plan()/charge() (e.g. repro.tenancy.TenantPolicy) gets per-task
        # admit/defer/reject decisions applied before selection, and
        # executed carbon charged back per tenant.
        self._tenancy = (policy if callable(getattr(policy, "plan", None))
                         and callable(getattr(policy, "charge", None))
                         else None)
        self.batch_size = batch_size
        self.queue: List[Task] = []
        # Budget-deferred tasks parked until their tenant's next accounting
        # period: (wake_hour, task) in decision order. Drained by
        # pop_ripe() (the sim driver) or automatically by run_until().
        self.deferred: List[tuple] = []
        # Per-drained-task outcomes of the last step(), for drivers that
        # must track rejected/deferred work: a list of
        # ("done", TaskResult) | ("reject", reason) | ("defer", wake_hour)
        # in drained order — or None, meaning every drained task produced
        # a TaskResult in order (the tenancy-free fast path pays no
        # per-task Python to say so). After a step that raised, entries
        # cover the consumed tasks and None marks requeued ones.
        self.last_outcomes: Optional[List[tuple]] = None
        self.monitor = monitor or CarbonMonitor(provider=self.provider)
        if self.monitor.provider is None:
            # Caller-supplied provider-less monitor: adopt the engine's
            # provider so both ledgers (cluster execution and monitor
            # billing) read the same, possibly time-varying, signal.
            self.monitor.provider = self.provider
        elif self.monitor.provider is not self.provider:
            # A monitor wired to a DIFFERENT provider would silently bill
            # from the wrong grid signal; that is only sound if every
            # cluster region is pre-registered with a pinned intensity.
            for name in cluster.nodes:
                acc = self.monitor.regions.get(name)
                if acc is None or not acc.pinned:
                    raise ValueError(
                        "caller-supplied monitor is wired to a different "
                        f"CarbonIntensityProvider and region {name!r} is "
                        "not pinned; share the engine's provider or pin "
                        "every cluster region explicitly")
        for name in cluster.nodes:
            if name not in self.monitor.regions:
                # same PUE as the cluster's execution ledger, so totals and
                # per_region carbon agree
                self.monitor.register_region(name, pue=cluster.pue)
        # Cheap always-on step accounting (surfaced by report()): steps
        # drained and cumulative done/reject/defer verdict totals ("dead"
        # and "retry" keys appear only once such an outcome occurred, so
        # pre-resilience report consumers see an unchanged dict).
        self._steps = 0
        self._outcome_totals = {"done": 0, "reject": 0, "defer": 0}
        # Requeue-loop guard (DESIGN.md §10): a task failing at the queue
        # head `max_requeues` consecutive times stops re-raising and is
        # consumed as a ("dead", reason) outcome instead — submitted work
        # is never silently lost, but a permanently infeasible/unknown-node
        # task can no longer livelock retrying callers. The first
        # max_requeues-1 failures raise exactly as before.
        if max_requeues < 1:
            raise ValueError("max_requeues must be >= 1")
        self.max_requeues = max_requeues
        self._fail_task = None
        self._fail_count = 0
        self.dead_letters: List[tuple] = []     # (task, reason)
        # Failure-aware scheduling (DESIGN.md §10): a repro.resilience.
        # Resilience attaches the availability mask / circuit breakers to
        # the cluster's FeatureCache, gates every placement against the
        # ground-truth down set (failover re-placement), and converts
        # unplaceable tasks into backoff retries that dead-letter after
        # max_attempts. None (the default) keeps every path bit-identical.
        self.resilience = resilience
        self._attempts: Dict[int, int] = {}     # id(task) -> attempts so far
        if resilience is not None:
            resilience.bind(self)
        # Observability hub (DESIGN.md §9): a repro.obs.Observability with
        # any pillar enabled; None (the default) keeps every path
        # bit-identical at the cost of one `is not None` check per phase.
        self.obs = obs if obs is not None and obs.enabled else None
        self._exec_snapshot = None
        # Per-step execution columns (DESIGN.md §11): after a fully
        # successful batched-execute step, ``(uniq_nodes, inverse,
        # latency_ms, energy_kwh, carbon_g)`` arrays carrying the same
        # floats the step's TaskResults do — the sim driver's columnar
        # record path consumes them instead of re-gathering O(B)
        # attributes. None whenever the last step used the scalar path,
        # partially failed, or went through tenancy admission.
        self.last_exec = None
        # Original-batch positions the resilience gate re-placed off a
        # down/unknown node in the last step (DESIGN.md §12) — the sim
        # driver's JourneyTrace counts failover hops from this. None when
        # the gate did not fire or nothing needed re-placement.
        self.last_failover_pos = None
        if self.obs is not None:
            self._wire_obs()

    def _wire_obs(self) -> None:
        """Attach the enabled obs pillars to the policy's duck-typed hooks
        (`capture_scores` publishes winning/runner-up totals on
        ``policy.last_scores``; `profiler` receives featurize/score
        spans), and resolve the engine's mode index for the trace."""
        obs, pol = self.obs, self.policy
        if obs.trace is not None and hasattr(pol, "capture_scores"):
            pol.capture_scores = True
        if obs.profiler is not None and hasattr(pol, "profiler"):
            pol.profiler = obs.profiler
        # == repro.obs.MODE_LABELS == repro.tenancy.spec.MODE_ORDER
        labels = ("performance", "balanced", "green")
        self._mode_idx = next((i for i, m in enumerate(labels)
                               if MODES[m] == self.weights), -1)

    # -- request lifecycle -------------------------------------------------
    def submit(self, task: Task) -> "CarbonEdgeEngine":
        self.queue.append(task)
        return self

    def submit_many(self, tasks: Sequence[Task]) -> "CarbonEdgeEngine":
        self.queue.extend(tasks)
        return self

    def peek(self, limit: Optional[int] = None) -> List[Task]:
        """The tasks the next :meth:`step` would drain, without dequeuing —
        a public inspection hook for drivers and operators (the bundled
        sim driver mirrors the queue itself and steps with ``limit``)."""
        b = limit if limit is not None else (self.batch_size or len(self.queue))
        return list(self.queue[:b])

    def step(self, now_hour: float = 0.0,
             limit: Optional[int] = None) -> List[TaskResult]:
        """Place and execute one batch of pending tasks.

        Selection for the whole batch is a single ``select_batch`` call —
        with the default VectorizedPolicy that is one (B, N, 8) featurize
        plus one kernel/scorer invocation, not B Python loops. ``limit``
        overrides ``batch_size`` for this call (partial drain — the sim
        driver steps exactly the tasks whose arrival events have fired).
        """
        self.last_outcomes = None
        self._exec_snapshot = None
        self.last_exec = None
        self.last_failover_pos = None
        if not self.queue:
            return []
        b = limit if limit is not None else (self.batch_size or len(self.queue))
        batch, self.queue = self.queue[:b], self.queue[b:]
        results: List[TaskResult] = []
        self._steps += 1
        if self._tenancy is not None:
            return self._step_tenancy(batch, now_hour, results)
        obs = self.obs
        prof = obs.profiler if obs is not None else None
        res = self.resilience
        outcomes = exec_pos = None   # set iff the resilience gate fired
        exec_batch: Sequence[Task] = batch
        try:
            if res is not None:
                res.tick(now_hour)
            with span(prof, "select"):
                choices = self.policy.select_batch(
                    self.cluster, batch, self.weights,
                    provider=self.provider, now_hour=now_hour)
            # Partitioned-execution hook (DESIGN.md §8): a policy exposing
            # execution_latency_ms (e.g. repro.partition.PartitionPolicy)
            # makes the engine execute and bill only the offloaded
            # segment's effective latency. Both execute paths consume the
            # same array, preserving batched/scalar parity.
            eff_fn = getattr(self.policy, "execution_latency_ms", None)
            base_override = eff_fn(batch) if eff_fn is not None else None
            # Failure-aware gate (DESIGN.md §10): only when something is
            # actually wrong — a ground-truth down node or an unplaceable
            # task — otherwise the zero-fault path is untouched.
            if res is not None and (res.down or None in choices):
                outcomes = [None] * len(batch)
                (exec_batch, choices, base_override,
                 exec_pos, _, _) = self._apply_resilience(
                     batch, choices, base_override, now_hour, outcomes,
                     list(range(len(batch))))
            if self.batch_execute:
                self._execute_batched(exec_batch, choices, now_hour,
                                      results, base_override)
            else:
                self._execute_scalar(exec_batch, choices, now_hour, results,
                                     base_override)
            if res is not None:
                if res.health.suspect:
                    res.note_success(set(choices[:len(results)]))
                if self._attempts:
                    for t in exec_batch:
                        self._attempts.pop(id(t), None)
        except BaseException as err:
            tail = list(exec_batch[len(results):])
            self._outcome_totals["done"] += len(results)
            dead = (tail[0] if tail and self._note_failure(tail[0])
                    else None)
            if dead is None:
                # On ANY failure (infeasible node, provider KeyError,
                # execution error) put everything not successfully executed
                # back at the head of the queue, so submitted work is never
                # silently lost.
                self.queue = tail + self.queue
                if outcomes is not None:
                    for j, r in zip(exec_pos, results):
                        outcomes[j] = ("done", r)
                    self.last_outcomes = outcomes
                raise
            # max_requeues-th consecutive failure of the same head task:
            # consume it as a dead letter instead of requeuing it into an
            # infinite raise/requeue loop (DESIGN.md §10)
            reason = f"{type(err).__name__}: {err}"
            self._record_dead(dead, reason)
            if outcomes is None:
                self.queue = tail[1:] + self.queue
                self.last_outcomes = ([("done", r) for r in results]
                                      + [("dead", reason)])
            else:
                # gate-fired step: park the unexecuted survivors as
                # immediate retries so every consumed position carries an
                # outcome (drivers stay aligned with the drained batch)
                for j, r in zip(exec_pos, results):
                    outcomes[j] = ("done", r)
                outcomes[exec_pos[len(results)]] = ("dead", reason)
                for j, t in zip(exec_pos[len(results) + 1:], tail[1:]):
                    self.deferred.append((now_hour, t))
                    self._outcome_totals["retry"] = \
                        self._outcome_totals.get("retry", 0) + 1
                    outcomes[j] = ("retry", now_hour)
                self.last_outcomes = outcomes
            return results
        self._outcome_totals["done"] += len(results)
        if outcomes is not None:
            for j, r in zip(exec_pos, results):
                outcomes[j] = ("done", r)
            self.last_outcomes = outcomes
        if obs is not None:
            # success-only (failed steps requeue and re-trace on retry)
            self._obs_record_step(obs, results, now_hour)
        return results

    def _note_failure(self, task) -> bool:
        """Track the consecutive-failure streak of the task at the failure
        point; True once it has exhausted ``max_requeues`` attempts."""
        if task is self._fail_task:
            self._fail_count += 1
        else:
            self._fail_task = task
            self._fail_count = 1
        if self._fail_count < self.max_requeues:
            return False
        self._fail_task = None
        self._fail_count = 0
        return True

    def _record_dead(self, task, reason: str) -> None:
        self._outcome_totals["dead"] = \
            self._outcome_totals.get("dead", 0) + 1
        self.dead_letters.append((task, reason))
        self._attempts.pop(id(task), None)

    def _apply_resilience(self, tasks, choices, base_override, now_hour,
                          outcomes, pos):
        """The failure-aware gate between selection and execution
        (DESIGN.md §10). Two stages:

        1. **failover**: any task placed onto a ground-truth-down (or
           unknown) node is a *contact failure* — breaker accounting plus
           detection-by-contact masking — and its subset is re-scored in
           one batched ``select_batch`` against the updated availability
           mask. A partition policy re-bills failed-over tasks through
           ``fallback_latency_ms`` (the cut-0 full-offload column): the
           stranded split is discarded and the whole model re-runs on the
           new node.
        2. **retry/dead-letter**: tasks still unplaceable park on
           ``self.deferred`` with capped exponential backoff (a
           ``("retry", wake)`` outcome) until ``max_attempts``, then
           dead-letter.

        ``outcomes`` (full original-batch length) is written in place at
        the removed tasks' ``pos`` entries. Returns the placed subset:
        ``(tasks, choices, base_override, pos, keep, removed)`` with
        ``keep``/``removed`` indexing the *incoming* lists.
        """
        res = self.resilience
        down = res.down
        nodes = self.cluster.nodes
        choices = list(choices)
        bad = [i for i, ch in enumerate(choices)
               if ch is not None and (ch in down or ch not in nodes)]
        if bad:
            self.last_failover_pos = [pos[i] for i in bad]
            for n in {choices[i] for i in bad}:
                res.contact_failure(n, now_hour)
            sub = [tasks[i] for i in bad]
            sub_choices = self.policy.select_batch(
                self.cluster, sub, self.weights, provider=self.provider,
                now_hour=now_hour)
            fb = getattr(self.policy, "fallback_latency_ms", None)
            if base_override is not None:
                base_override = np.array(base_override, dtype=float)
            for k, i in enumerate(bad):
                choices[i] = sub_choices[k]
                if (sub_choices[k] is not None and fb is not None
                        and base_override is not None):
                    base_override[i] = fb(tasks[i])
        keep = list(range(len(tasks)))
        removed: List[int] = []
        if None in choices:
            for i, ch in enumerate(choices):
                if ch is not None:
                    continue
                t = tasks[i]
                attempt = self._attempts.pop(id(t), 0) + 1
                if attempt >= res.max_attempts:
                    reason = f"no feasible node after {attempt} attempts"
                    self._record_dead(t, reason)
                    outcomes[pos[i]] = ("dead", reason)
                else:
                    self._attempts[id(t)] = attempt
                    wake = now_hour + res.backoff_hours(attempt)
                    self.deferred.append((wake, t))
                    self._outcome_totals["retry"] = \
                        self._outcome_totals.get("retry", 0) + 1
                    outcomes[pos[i]] = ("retry", wake)
                removed.append(i)
            keep = [i for i, ch in enumerate(choices) if ch is not None]
            tasks = [tasks[i] for i in keep]
            choices = [choices[i] for i in keep]
            if base_override is not None:
                base_override = np.asarray(base_override, dtype=float)[keep]
            pos = [pos[i] for i in keep]
        return tasks, choices, base_override, pos, keep, removed

    def _step_tenancy(self, batch: Sequence[Task], now_hour: float,
                      results: List[TaskResult]) -> List[TaskResult]:
        """Admission-controlled step (DESIGN.md §7): the tenant policy
        plans admit/defer/reject for the drained batch, rejected tasks
        are dropped (counted in the registry), deferred tasks park on
        ``self.deferred`` until their wake hour, and only the admitted
        subset is placed (mode-escalated), executed and billed — with the
        executed prefix's carbon charged back per tenant even when the
        batch fails mid-way."""
        obs = self.obs
        prof = obs.profiler if obs is not None else None
        res = self.resilience
        try:
            if res is not None:
                res.tick(now_hour)
            with span(prof, "plan"):
                plan = self.policy.plan(self.cluster, batch,
                                        provider=self.provider,
                                        now_hour=now_hour)
        except BaseException:
            # admission itself failed (e.g. a partial-coverage provider
            # KeyError): nothing was consumed, so the whole batch requeues
            # — the same never-silently-lost invariant as the
            # tenancy-free path
            self.queue = list(batch) + self.queue
            raise
        outcomes: List[tuple] = [None] * len(batch)
        if plan.all_admitted:
            aidx = None
            exec_tasks: Sequence[Task] = batch
        else:
            from repro.tenancy.policy import DEFER as _DEFER
            from repro.tenancy.policy import REJECT as _REJECT
            aidx = plan.admitted_index()
            exec_tasks = [batch[i] for i in aidx]
            rej = np.nonzero(plan.actions == _REJECT)[0]
            deferred = np.nonzero(plan.actions == _DEFER)[0]
            for i in rej:
                outcomes[i] = ("reject", "carbon budget exhausted")
            for i in deferred:
                w = float(plan.wake_hour[i])
                self.deferred.append((w, batch[i]))
                outcomes[i] = ("defer", w)
            # rejected/deferred verdicts are consumed whatever happens next
            self._outcome_totals["reject"] += int(rej.size)
            self._outcome_totals["defer"] += int(deferred.size)
        # admitted tenant ids / original-batch positions, kept consistent
        # with exec_tasks through the resilience gate's rewrites
        sel = np.asarray(plan.tenant_idx if aidx is None
                         else plan.tenant_idx[aidx])
        pos = (list(range(len(batch))) if aidx is None
               else [int(i) for i in aidx])
        gate_fired = False
        dead_reason = None
        try:
            with span(prof, "select"):
                full = self.policy.select_admitted(
                    self.cluster, batch, plan, self.weights,
                    provider=self.provider, now_hour=now_hour)
            choices = (full if aidx is None
                       else [full[i] for i in aidx])
            if res is not None and (res.down or None in choices):
                gate_fired = True
                (exec_tasks, choices, _, pos,
                 keep, removed) = self._apply_resilience(
                     exec_tasks, choices, None, now_hour, outcomes, pos)
                if removed:
                    # retried/dead tasks get re-planned (or never run):
                    # reverse their admitted counting now
                    self.policy.registry.uncount_admitted(sel[removed])
                    sel = sel[keep]
            if self.batch_execute:
                self._execute_batched(exec_tasks, choices, now_hour, results)
            else:
                self._execute_scalar(exec_tasks, choices, now_hour, results)
            if res is not None:
                if res.health.suspect:
                    res.note_success(set(choices[:len(results)]))
                if self._attempts:
                    for t in exec_tasks:
                        self._attempts.pop(id(t), None)
        except BaseException as err:
            requeued = list(exec_tasks[len(results):])
            if requeued:
                # requeued tasks get re-planned (and re-counted) on the
                # retry, so reverse this plan's admitted counting for them
                self.policy.registry.uncount_admitted(sel[len(results):])
            dead = (requeued[0] if requeued
                    and self._note_failure(requeued[0]) else None)
            if dead is None:
                self.queue = requeued + self.queue
                raise
            # attempt cap reached: consume the poisoned head as a dead
            # letter (DESIGN.md §10) and keep the step's results
            dead_reason = f"{type(err).__name__}: {err}"
            self._record_dead(dead, dead_reason)
            # park the unexecuted survivors as immediate retries so every
            # consumed position carries an outcome — admitted positions can
            # precede deferred/rejected ones, so a silent requeue would
            # desynchronize outcome-tracking drivers from the drained batch
            for j, t in zip(pos[len(results) + 1:], requeued[1:]):
                self.deferred.append((now_hour, t))
                self._outcome_totals["retry"] = \
                    self._outcome_totals.get("retry", 0) + 1
                outcomes[j] = ("retry", now_hour)
        finally:
            # charge exactly the executed prefix — on a mid-batch failure
            # that is the same set the cluster/monitor ledgers billed
            if results:
                self.policy.charge(sel[:len(results)],
                                   [r.carbon_g for r in results], now_hour)
            # publish verdicts even when execution raised mid-batch:
            # rejected/deferred tasks were consumed, so a caller tracking
            # per-request state must still see them; None marks the
            # requeued admitted tail
            for j, r in zip(pos, results):
                outcomes[j] = ("done", r)
            if dead_reason is not None:
                outcomes[pos[len(results)]] = ("dead", dead_reason)
            self.last_outcomes = outcomes
            self._outcome_totals["done"] += len(results)
        if dead_reason is not None:
            return results
        if obs is not None:
            # success-only, like the tenancy-free path
            self._obs_record_tenancy(obs, batch, plan, results, now_hour,
                                     aidx,
                                     exec_pos=pos if gate_fired else None)
        return results

    def pop_ripe(self, now_hour: float) -> List[Task]:
        """Remove and return budget-deferred tasks whose wake hour has
        arrived, in park order — the caller resubmits them (the sim
        driver does this on its tenancy DEFER_WAKE event;
        :meth:`run_until` does it automatically)."""
        if not self.deferred:
            return []
        ripe = [t for w, t in self.deferred if w <= now_hour]
        if ripe:
            self.deferred = [(w, t) for w, t in self.deferred
                             if w > now_hour]
        return ripe

    def _execute_scalar(self, batch: Sequence[Task],
                        choices: Sequence[Optional[str]], now_hour: float,
                        results: List[TaskResult],
                        base_override=None) -> None:
        """Per-task execute+bill loop — the parity oracle the batched path
        is bit-identical to (cluster/monitor ledgers, log, requeue state).
        ``base_override`` replaces each task's base latency (the policy's
        partitioned effective latency), same array the batched path uses."""
        for i, (task, node) in enumerate(zip(batch, choices)):
            if node is None:
                # Already-executed results travel on the exception; the
                # infeasible task and the tail are requeued by step().
                raise NoFeasibleNodeError(results)
            st = self.cluster.nodes[node]
            # Resolve every billing input BEFORE executing, so a
            # provider/monitor lookup failure cannot leave a task
            # executed in the cluster ledger yet requeued for a retry
            # (which would double-execute it).
            exec_intensity = self.provider.intensity(node, now_hour)
            self.monitor.billing_intensity(node, now_hour)
            base = (task.base_latency_ms if base_override is None
                    else float(base_override[i]))
            st.running += 1
            try:
                res = self.cluster.execute(
                    node, base, distributed=True,
                    intensity=exec_intensity)
            finally:
                st.running -= 1
            self.monitor.record_energy(node, res.energy_kwh,
                                       hour=now_hour)
            results.append(res)

    def _probe_intensities(self, nodes: Sequence[str], now_hour: float):
        """Scalar-order resolution fallback: probe node-by-node *in first-
        appearance order* so a failure cuts the batch at exactly the task
        the scalar loop would have failed on. Returns
        ``(exec_int, bill_int, n_ok, error)``: dicts covering the nodes of
        the first ``n_ok`` tasks, plus the captured per-node exception."""
        exec_int, bill_int = {}, {}
        for i, n in enumerate(nodes):
            if n in exec_int:
                continue
            try:
                # exactly the scalar loop's resolution order: node lookup,
                # provider read, monitor billing probe
                self.cluster.nodes[n]
                ei = self.provider.intensity(n, now_hour)
                bi = self.monitor.billing_intensity(n, now_hour)
            except Exception as err:
                return exec_int, bill_int, i, err
            exec_int[n] = ei
            bill_int[n] = bi
        return exec_int, bill_int, len(nodes), None

    def _execute_batched(self, batch: Sequence[Task],
                         choices: Sequence[Optional[str]], now_hour: float,
                         results: List[TaskResult],
                         base_override=None) -> None:
        """Vectorized execute+bill (DESIGN.md §6): one
        ``cluster.execute_batch`` + one ``monitor.record_energy_batch`` for
        the feasible prefix — O(distinct nodes) Python work per step
        instead of O(B) — preserving the scalar loop's mid-batch failure
        semantics: tasks before the first infeasible/unresolvable one are
        executed and billed, the rest requeue via step()'s handler.

        Every billing input resolves BEFORE anything executes (the scalar
        loop's commit rule): execution intensity through one batched
        provider read over the distinct chosen nodes, billing intensity
        through one ``monitor.billing_intensity_batch`` — degrading to the
        per-node probe (``_probe_intensities``) when any node is unknown
        or uncovered, so the failing task index matches the scalar loop's.
        """
        # Cut at the first infeasible task: the scalar loop executes
        # everything before it, then raises with those results attached.
        try:
            cut = choices.index(None)
            failure = NoFeasibleNodeError(results)
        except ValueError:
            cut, failure = len(batch), None
        nodes = list(choices[:cut])
        groups = ev = bv = None
        if nodes:
            groups = np.unique(np.asarray(nodes, dtype=object),
                               return_inverse=True)
            uniq, inverse = groups
            try:
                for n in uniq:
                    if n not in self.cluster.nodes:
                        raise KeyError(n)
                ev = np.asarray(intensity_batch(self.provider, list(uniq),
                                                now_hour), dtype=float)
                bv = self.monitor.billing_intensity_batch(list(uniq),
                                                          now_hour)
            except Exception:
                exec_int, bill_int, n_ok, err = self._probe_intensities(
                    nodes, now_hour)
                if err is None:
                    # batch read failed but every per-node probe succeeded
                    # (inconsistent custom provider): use the probed values
                    ev = np.array([exec_int[n] for n in uniq], dtype=float)
                    bv = np.array([bill_int[n] for n in uniq], dtype=float)
                else:
                    cut, failure = n_ok, err
                    nodes = nodes[:cut]
                    if nodes:
                        groups = np.unique(np.asarray(nodes, dtype=object),
                                           return_inverse=True)
                        uniq, inverse = groups
                        ev = np.array([exec_int[n] for n in uniq],
                                      dtype=float)
                        bv = np.array([bill_int[n] for n in uniq],
                                      dtype=float)
        if nodes:
            obs = self.obs
            prof = obs.profiler if obs is not None else None
            base = (np.array([t.base_latency_ms for t in batch[:cut]],
                             dtype=float)
                    if base_override is None
                    else np.asarray(base_override[:cut], dtype=float))
            with span(prof, "execute"):
                res = self.cluster.execute_batch(
                    nodes, base, distributed=True, intensities=ev[inverse],
                    groups=groups)
            # The billed energy is recomputed through the cluster's own
            # cost model (the same call execute_batch makes) rather than
            # gathered back out of the B result objects — same floats, no
            # O(B) attribute reads, one source of truth for the math.
            with span(prof, "bill"):
                lat_ms, e_kwh = self.cluster.latency_energy(base,
                                                            distributed=True)
                self.monitor.record_energy_batch(
                    nodes, e_kwh, hour=now_hour, intensities=bv[inverse],
                    groups=groups)
            results.extend(res)
            if failure is None:
                # whole batch executed: publish the step's execution
                # columns for the sim driver's columnar record path
                # (DESIGN.md §11). carbon_g here is the same elementwise
                # expression execute_batch evaluated, so the arrays carry
                # the exact floats the TaskResults do.
                self.last_exec = (uniq, inverse, lat_ms, e_kwh,
                                  carbon_g(e_kwh, ev[inverse],
                                           self.cluster.pue))
            if obs is not None and (obs.trace is not None
                                    or obs.metrics is not None
                                    or obs.rollups is not None):
                # stash the already-computed batched arrays so the trace/
                # metrics record after a successful step adds no provider
                # re-reads or O(B) Python (DESIGN.md §9)
                self._exec_snapshot = (uniq, inverse, ev, bv, e_kwh)
        if failure is not None:
            # `results` is the shared list step() requeues against, so the
            # exception's executed-prefix view matches the scalar loop's.
            raise failure

    def run(self, tasks: Optional[Sequence[Task]] = None, *,
            task: Optional[Task] = None, iterations: int = 1,
            now_hour: float = 0.0) -> Dict:
        """Submit ``tasks`` (or ``iterations`` copies of ``task``, default
        one), drain the queue in batched steps, and return :meth:`report`.

        .. deprecated:: the whole queue is drained at a single frozen
           ``now_hour``, which silently mis-bills time-varying providers
           (every batch reads the grid at the submission instant, however
           long the drain takes). With a non-static provider prefer
           :meth:`run_until` (minimal time-advancing drain) or the full
           event-driven :class:`repro.sim.AsyncEngineDriver`; this shim
           stays exact for the static paper scenarios.
        """
        if not isinstance(self.provider, StaticProvider):
            warnings.warn(
                "CarbonEdgeEngine.run drains the queue at one frozen "
                "now_hour; with a time-varying CarbonIntensityProvider use "
                "run_until() or repro.sim.AsyncEngineDriver so billing "
                "tracks simulated time", DeprecationWarning, stacklevel=2)
        if tasks is not None:
            self.submit_many(tasks)
        if task is not None:
            self.submit_many([task] * iterations)
        while self.queue:
            self.step(now_hour)
        if self.deferred:
            # run() freezes the clock, so budget-deferred work can never
            # reach its wake hour here — tell the caller instead of
            # silently dropping it (run_until()/pop_ripe() resume it)
            warnings.warn(
                f"CarbonEdgeEngine.run left {len(self.deferred)} "
                "budget-deferred task(s) parked: the frozen now_hour "
                "never reaches their accounting-period wake; use "
                "run_until() or pop_ripe() to resume them",
                RuntimeWarning, stacklevel=2)
        return self.report()

    def run_until(self, end_hour: float, *, start_hour: float = 0.0,
                  limit: Optional[int] = None) -> Dict:
        """Drain the queue in batched steps while *advancing simulated
        time*: each batch is billed at the hour the previous batches'
        measured service time has accumulated to (the cluster is a serial
        executor, so a batch of total latency L ms advances the clock by
        L / 3.6e6 hours). Stops when the queue is empty or the clock
        passes ``end_hour`` (the remainder stays queued). Returns
        :meth:`report` plus the final clock under ``"end_hour"``.

        This is the minimal time-advancing replacement for :meth:`run`;
        arrival dynamics, deferral and queueing metrics live in the full
        event-driven :class:`repro.sim.AsyncEngineDriver`.
        """
        now = start_hour
        while now < end_hour:
            self.queue[:0] = self.pop_ripe(now)
            if not self.queue:
                # idle but budget-deferred work exists: jump the clock to
                # the earliest wake inside the window
                wake = min((w for w, _ in self.deferred if w < end_hour),
                           default=None)
                if wake is None:
                    break
                now = max(now, wake)
                continue
            qlen = len(self.queue)
            results = self.step(now, limit=limit)
            if not results and len(self.queue) >= qlen:
                # zero-size limit or a step that drained nothing: no
                # progress is possible, bail instead of spinning forever
                break
            now += sum(r.latency_ms for r in results) / 3.6e6
        rep = self.report()
        rep["end_hour"] = now
        return rep

    # -- observability (DESIGN.md §9) --------------------------------------
    def _obs_metrics_nodes(self, metrics, uniq, inverse, carbon) -> None:
        """Per-node task and carbon counters from the step's grouped
        arrays: O(distinct nodes) label interning, scatter-add updates."""
        counts = np.bincount(inverse, minlength=len(uniq))
        csum = np.bincount(inverse, weights=carbon, minlength=len(uniq))
        for name, help_, vals in (
                ("engine_tasks_total", "tasks executed per node", counts),
                ("engine_carbon_g_total",
                 "carbon billed per node (gCO2)", csum)):
            fam = metrics.counter(name, help_, ("node",))
            fam.inc_at(fam.rows([(str(n),) for n in uniq]), vals)

    def _obs_metrics_depths(self, metrics) -> None:
        metrics.gauge("engine_queue_depth",
                      "tasks pending in the engine queue"
                      ).set(float(len(self.queue)))
        metrics.gauge("engine_deferred_depth",
                      "budget-deferred tasks parked"
                      ).set(float(len(self.deferred)))

    def _obs_intervals(self, uniq, inverse, now_hour):
        """Conformal (lo, hi) per task when the provider carries a
        calibrator, else (None, None) — zero-width intervals from plain
        providers carry no information, so skip the extra read."""
        if getattr(self.provider, "conformal", None) is None:
            return None, None
        lo, hi = intensity_interval_batch(self.provider, list(uniq),
                                          now_hour)
        return (np.asarray(lo, dtype=float)[inverse],
                np.asarray(hi, dtype=float)[inverse])

    def _obs_record_step(self, obs, results, now_hour: float) -> None:
        """Trace + metrics for one successful tenancy-free step, fed from
        the batched-execute snapshot (no per-task Python; the scalar
        parity oracle falls back to gathering from its B results)."""
        trace, metrics = obs.trace, obs.metrics
        roll = obs.rollups
        if trace is None and metrics is None and roll is None:
            return
        B = len(results)
        if B == 0:
            return
        with span(obs.profiler, "observe"):
            snap = self._exec_snapshot
            if snap is not None:
                uniq, inverse, ev, bv, e_kwh = snap
                ev_t = ev[inverse]
                # same expression execute_batch billed with — identical floats
                carbon = carbon_g(e_kwh, ev_t, self.cluster.pue)
            else:
                uniq, inverse = np.unique(
                    np.asarray([r.node for r in results], dtype=object),
                    return_inverse=True)
                ev = np.asarray(intensity_batch(self.provider, list(uniq),
                                                now_hour), dtype=float)
                ev_t = ev[inverse]
                bv = np.asarray(self.monitor.billing_intensity_batch(
                    list(uniq), now_hour), dtype=float)
                carbon = np.asarray([r.carbon_g for r in results], dtype=float)
                e_kwh = (np.asarray([r.energy_kwh for r in results], dtype=float)
                         if roll is not None else None)
            if roll is not None:
                roll.fold_exec(now_hour, carbon, e_kwh)
                roll.fold_verdicts(now_hour, (B, 0, 0, 0, 0))  # all done
            if trace is not None:
                lo, hi = self._obs_intervals(uniq, inverse, now_hour)
                score = runner = cut = None
                ls = getattr(self.policy, "last_scores", None)
                if ls is not None and ls.get("score") is not None \
                        and len(ls["score"]) == B:
                    score, runner = ls["score"], ls.get("runner_up")
                    cut = ls.get("cut")
                trace.record_batch(
                    step=self._steps, hour=now_hour,
                    verdict=np.zeros(B, dtype=np.int8),   # all done
                    node=trace.intern_names(uniq)[inverse],
                    cut=cut, mode=self._mode_idx,
                    score=score, runner_up=runner,
                    intensity=ev_t, interval_lo=lo, interval_hi=hi,
                    intensity_billed=bv[inverse], carbon_g=carbon)
            if metrics is not None:
                self._obs_metrics_nodes(metrics, uniq, inverse, carbon)
                metrics.counter("engine_outcomes_total",
                                "step outcomes by verdict", ("verdict",)
                                ).inc(B, labels=("done",))
                self._obs_metrics_depths(metrics)

    def _obs_record_tenancy(self, obs, batch, plan, results, now_hour,
                            aidx, exec_pos=None) -> None:
        """Trace + metrics for one successful admission-controlled step:
        full-length rows (rejected/deferred tasks get their verdict with
        no placement), executed columns scattered at the admitted
        positions from the batched-execute snapshot. ``exec_pos`` (set
        when the resilience gate rewrote the admitted subset) overrides
        the executed positions and sources verdicts from the published
        outcomes, so retried/dead rows trace as such."""
        trace, metrics = obs.trace, obs.metrics
        roll = obs.rollups
        if trace is None and metrics is None and roll is None:
            return
        with span(obs.profiler, "observe"):
            from repro.tenancy.policy import ADMIT as _ADMIT
            from repro.tenancy.policy import REJECT as _REJECT
            B = len(batch)
            if exec_pos is not None:
                from repro.obs.trace import VERDICT_LABELS
                codes = {k: c for c, k in enumerate(VERDICT_LABELS)}
                verdict = np.array([codes[o[0]] for o in self.last_outcomes],
                                   dtype=np.int8)
                pos_exec = np.asarray(exec_pos[:len(results)], dtype=int)
            else:
                # explicit action -> trace-verdict map (the two encodings order
                # DEFER/REJECT differently)
                verdict = np.where(
                    plan.actions == _ADMIT, 0,
                    np.where(plan.actions == _REJECT, 1, 2)).astype(np.int8)
                pos_exec = (np.arange(len(results)) if aidx is None
                            else np.asarray(aidx))
            uniq = inverse = carbon = e_kwh = None
            if results:
                snap = self._exec_snapshot
                if snap is not None:
                    uniq, inverse, ev, bv, e_kwh = snap
                    ev_t = ev[inverse]
                    carbon = carbon_g(e_kwh, ev_t, self.cluster.pue)
                else:
                    uniq, inverse = np.unique(
                        np.asarray([r.node for r in results], dtype=object),
                        return_inverse=True)
                    ev = np.asarray(intensity_batch(self.provider, list(uniq),
                                                    now_hour), dtype=float)
                    ev_t = ev[inverse]
                    bv = np.asarray(self.monitor.billing_intensity_batch(
                        list(uniq), now_hour), dtype=float)
                    carbon = np.asarray([r.carbon_g for r in results],
                                        dtype=float)
                    e_kwh = (np.asarray([r.energy_kwh for r in results],
                                        dtype=float)
                             if roll is not None else None)
            if roll is not None:
                if results:
                    roll.fold_exec(now_hour, carbon, e_kwh)
                    reg = getattr(self.policy, "registry", None)
                    index = getattr(reg, "index", None)
                    if index:
                        names = np.asarray(sorted(index, key=index.get),
                                           dtype=object)
                        tmap = roll.intern_tenants(names)
                        tidx = np.asarray(plan.tenant_idx)[pos_exec]
                        tagged = tidx >= 0
                        if tagged.any():
                            roll.fold_tenant_spend(now_hour, tmap[tidx[tagged]],
                                                   carbon[tagged])
                roll.fold_verdicts(
                    now_hour, np.bincount(verdict, minlength=5)[:5])
            if trace is not None:
                node = np.full(B, -1, dtype=np.int32)
                intens = np.full(B, np.nan)
                billed = np.full(B, np.nan)
                carb = np.full(B, np.nan)
                ilo = ihi = None
                if results:
                    node[pos_exec] = trace.intern_names(uniq)[inverse]
                    intens[pos_exec] = ev_t
                    billed[pos_exec] = bv[inverse]
                    carb[pos_exec] = carbon
                    lo, hi = self._obs_intervals(uniq, inverse, now_hour)
                    if lo is not None:
                        ilo = np.full(B, np.nan)
                        ihi = np.full(B, np.nan)
                        ilo[pos_exec] = lo
                        ihi[pos_exec] = hi
                # -1 (untagged / no escalation) means the engine's own mode
                modes = np.where(plan.modes >= 0, plan.modes,
                                 self._mode_idx).astype(np.int8)
                tenant = None
                reg = getattr(self.policy, "registry", None)
                index = getattr(reg, "index", None)
                if index:
                    names = np.asarray(sorted(index, key=index.get),
                                       dtype=object)
                    tmap = trace.intern_names(names, kind="tenant")
                    tidx = np.asarray(plan.tenant_idx)
                    tenant = np.where(tidx >= 0,
                                      tmap[np.maximum(tidx, 0)],
                                      -1).astype(np.int32)
                score = runner = cut = None
                ls = getattr(self.policy, "last_scores", None)
                if ls is not None and ls.get("score") is not None \
                        and len(ls["score"]) == B:
                    score, runner = ls["score"], ls.get("runner_up")
                    cut = ls.get("cut")
                trace.record_batch(
                    step=self._steps, hour=now_hour, verdict=verdict,
                    node=node, cut=cut, mode=modes, tenant=tenant,
                    score=score, runner_up=runner,
                    intensity=intens, interval_lo=ilo, interval_hi=ihi,
                    intensity_billed=billed, carbon_g=carb,
                    expected_g=plan.expected_g)
            if metrics is not None:
                if results:
                    self._obs_metrics_nodes(metrics, uniq, inverse, carbon)
                fam = metrics.counter("engine_outcomes_total",
                                      "step outcomes by verdict", ("verdict",))
                for code, label in enumerate(
                        ("done", "reject", "defer", "dead", "retry")):
                    n = int((verdict == code).sum())
                    if n:
                        fam.inc(n, labels=(label,))
                self._obs_metrics_depths(metrics)

    # -- reporting ---------------------------------------------------------
    def report(self, deep: bool = False) -> Dict:
        rep = {
            "totals": self.cluster.totals(),
            "distribution": self.cluster.distribution(),
            "policy": self.policy.name,
            "per_region": self.monitor.report(),
            "steps": self._steps,
            "outcomes": dict(self._outcome_totals),
            "deferred_depth": len(self.deferred),
        }
        if self._tenancy is not None:
            rep["tenants"] = self._tenancy.registry.report()
        if self.resilience is not None or self.dead_letters:
            rep["resilience"] = {
                "dead_letters": len(self.dead_letters),
                "retrying": len(self._attempts),
            }
            if self.resilience is not None:
                rep["resilience"].update(self.resilience.report())
        if deep:
            rep["deep"] = self._report_deep()
        return rep

    def _report_deep(self) -> Dict:
        """Structured diagnostics (DESIGN.md §9): obs pillar summaries
        plus partition / deferral / conformal-coverage aggregates. A
        diagnostic call — may do O(retained-trace) work."""
        deep: Dict = {}
        obs = self.obs
        if obs is not None:
            if obs.profiler is not None:
                deep["profiler"] = obs.profiler.summary()
            if obs.trace is not None:
                deep["trace"] = obs.trace.stats()
                deep["conformal"] = obs.trace.conformal_coverage()
                cuts = obs.trace.cut_histogram()
                if cuts:
                    deep["partition"] = {"cut_histogram": cuts}
            if obs.journeys is not None:
                deep["journeys"] = obs.journeys.stats()
            if obs.rollups is not None:
                deep["rollups"] = obs.rollups.stats()
            if obs.alerts is not None:
                deep["alerts"] = obs.alerts.stats()
            if obs.metrics is not None:
                deep["metrics"] = obs.metrics.snapshot()
        deep["deferral"] = {
            "parked": len(self.deferred),
            "deferred_total": self._outcome_totals["defer"],
            "next_wake": (min(w for w, _ in self.deferred)
                          if self.deferred else None),
        }
        # last-batch partition decisions work without tracing too
        decisions = getattr(self.policy, "last_decisions", None)
        if decisions:
            hist: Dict[int, int] = {}
            for d in decisions:
                if d is not None:
                    hist[d.cut_index] = hist.get(d.cut_index, 0) + 1
            deep.setdefault("partition", {})["last_batch_cuts"] = hist
        return deep
