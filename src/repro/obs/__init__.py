"""repro.obs — always-available observability (DESIGN.md §9, §12).

Six pillars, each independently switchable and ``None`` when off:

- :class:`~repro.obs.trace.DecisionTrace` — column-oriented ring buffer
  of per-task scheduling decisions (node/cut/mode, winning vs runner-up
  score, intensity + conformal interval, admission verdict, carbon) with
  a deterministic JSONL exporter.
- :class:`~repro.obs.registry.MetricsRegistry` — numpy-column counters /
  gauges / histograms with Prometheus-style text exposition.
- :class:`~repro.obs.profiler.StepProfiler` — ``perf_counter`` spans
  around the engine/sim/serving phases, folded into per-phase histograms;
  each phase is also a ``carbonedge.*`` profiler annotation
  (:class:`~repro.obs.profiler.span`), so a ``jax.profiler`` capture
  shows it beside the device's operations.
- :class:`~repro.obs.journey.JourneyTrace` — per-request causal record
  keyed by sim task uid (arrival → verdicts → defer/wake → retry/failover
  → execute-or-dead-letter) with ``explain_journey`` forensics and a
  vectorized critical-path decomposition.
- :class:`~repro.obs.rollup.RollupStore` — fixed-width sim-time windows
  folding carbon/energy/SLO/verdict/tenant/availability columns into
  bounded-memory series (O(windows), not O(tasks)).
- :class:`~repro.obs.alerts.AlertEngine` — declarative threshold /
  burn-rate rules evaluated vectorized per rollup window, emitting a
  deterministic fire/resolve event log.

``Observability`` bundles them for threading through
``CarbonEdgeEngine(obs=...)``, ``ServingEngine(obs=...)`` and
``AsyncEngineDriver(obs=...)``. The disabled default costs one
``is not None`` check per instrumented site, plus one inactive profiler
annotation per profiled phase, and leaves every existing output
byte-identical (the sim ``to_text`` contract, enforced by ``gate_obs``);
this package imports only stdlib + numpy (JAX's profiler lazily, on the
first span) so the core/tenancy/partition layers can depend on it without
cycles.
"""
from __future__ import annotations

import logging
import sys
from typing import Dict, Optional, Sequence, Union

from repro.obs.alerts import (ALERT_KINDS, AlertEngine, AlertEvent,
                              AlertRule, default_rules)
from repro.obs.journey import (J_DEAD, J_DONE, J_OPEN, J_REJECT,
                               PARK_DEFER, PARK_RETRY, STATE_LABELS,
                               JourneyTrace)
from repro.obs.profiler import SPAN_EDGES_S, TRACE_PREFIX, StepProfiler, span
from repro.obs.registry import DEFAULT_EDGES, Family, MetricsRegistry
from repro.obs.rollup import VERDICT_COLS, RollupStore
from repro.obs.trace import (MODE_LABELS, VERDICT_DEAD, VERDICT_DEFER,
                             VERDICT_DONE, VERDICT_LABELS, VERDICT_REJECT,
                             VERDICT_RETRY, DecisionTrace)

__all__ = [
    "ALERT_KINDS", "AlertEngine", "AlertEvent", "AlertRule",
    "DEFAULT_EDGES", "DecisionTrace", "Family", "J_DEAD", "J_DONE",
    "J_OPEN", "J_REJECT", "JourneyTrace", "MetricsRegistry",
    "MODE_LABELS", "Observability", "PARK_DEFER", "PARK_RETRY",
    "RollupStore", "SPAN_EDGES_S", "STATE_LABELS", "StepProfiler",
    "TRACE_PREFIX",
    "VERDICT_COLS", "VERDICT_DEAD", "VERDICT_DEFER", "VERDICT_DONE",
    "VERDICT_LABELS", "VERDICT_REJECT", "VERDICT_RETRY", "console_logger",
    "default_rules", "span",
]


class Observability:
    """Hub carrying the enabled pillars; a pillar is ``None`` when off.

    Each argument accepts ``False`` (off), ``True`` (fresh default
    instance), or an existing instance to share between components."""

    def __init__(self, *,
                 trace: Union[bool, DecisionTrace] = False,
                 metrics: Union[bool, MetricsRegistry] = False,
                 profile: Union[bool, StepProfiler] = False,
                 journeys: Union[bool, JourneyTrace] = False,
                 rollups: Union[bool, RollupStore] = False,
                 alerts: Union[bool, AlertEngine] = False,
                 trace_capacity: int = 1 << 16,
                 rollup_window_hours: float = 0.25,
                 alert_rules: Optional[Sequence[AlertRule]] = None) -> None:
        self.trace = (trace if isinstance(trace, DecisionTrace)
                      else DecisionTrace(trace_capacity) if trace else None)
        self.metrics = (metrics if isinstance(metrics, MetricsRegistry)
                        else MetricsRegistry() if metrics else None)
        self.profiler = (profile if isinstance(profile, StepProfiler)
                         else StepProfiler() if profile else None)
        self.journeys = (journeys if isinstance(journeys, JourneyTrace)
                         else JourneyTrace() if journeys else None)
        self.rollups = (rollups if isinstance(rollups, RollupStore)
                        else RollupStore(rollup_window_hours)
                        if rollups else None)
        # Alerts need rollups to evaluate against; an AlertEngine without
        # a RollupStore is inert but harmless (evaluate is never called).
        self.alerts = (alerts if isinstance(alerts, AlertEngine)
                       else AlertEngine(alert_rules) if alerts else None)

    @classmethod
    def all(cls, trace_capacity: int = 1 << 16,
            rollup_window_hours: float = 0.25,
            alert_rules: Optional[Sequence[AlertRule]] = None
            ) -> "Observability":
        """Every pillar on — the ``gate_obs`` enabled configuration."""
        return cls(trace=True, metrics=True, profile=True,
                   journeys=True, rollups=True, alerts=True,
                   trace_capacity=trace_capacity,
                   rollup_window_hours=rollup_window_hours,
                   alert_rules=alert_rules)

    @property
    def enabled(self) -> bool:
        return (self.trace is not None or self.metrics is not None
                or self.profiler is not None or self.journeys is not None
                or self.rollups is not None or self.alerts is not None)

    def report(self) -> Dict:
        """JSON-ready summary of whatever pillars are on."""
        out: Dict = {}
        if self.trace is not None:
            out["trace"] = self.trace.stats()
        if self.profiler is not None:
            out["profiler"] = self.profiler.summary()
        if self.journeys is not None:
            out["journeys"] = self.journeys.stats()
        if self.rollups is not None:
            out["rollups"] = self.rollups.stats()
        if self.alerts is not None:
            out["alerts"] = self.alerts.stats()
        if self.metrics is not None:
            out["metrics"] = self.metrics.snapshot()
        return out


def console_logger(name: str, level: int = logging.INFO) -> logging.Logger:
    """Module-level logger with a plain-``%(message)s`` stdout handler on
    the shared ``repro`` root, so launch scripts keep their exact printed
    output under ``logging`` (SNIPPETS.md §1). Idempotent: the handler is
    attached once no matter how many modules call this."""
    logger = logging.getLogger(name)
    # attach to the shared "repro" ancestor when possible so one handler
    # serves the whole package; "__main__"-style names get their own
    root = (logging.getLogger("repro")
            if name == "repro" or name.startswith("repro.") else logger)
    if not any(getattr(h, "_repro_console", False) for h in root.handlers):
        handler = logging.StreamHandler(sys.stdout)
        handler.setFormatter(logging.Formatter("%(message)s"))
        handler._repro_console = True
        root.addHandler(handler)
        root.setLevel(level)
    logger.setLevel(level)
    return logger
