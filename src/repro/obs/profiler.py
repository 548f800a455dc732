"""Per-phase step profiler (DESIGN.md §9).

``perf_counter`` spans around the featurize/select/execute/bill phases of
``engine.step`` (and the sim driver's event batches) are folded into fixed
log-spaced histograms — count / total / min / max / per-bin counts per
phase — so the paper's 0.03 ms scheduling-overhead claim is a continuously
tracked artifact (``BENCH_obs.json``) instead of an ad-hoc benchmark.

The accumulator is O(1) per span (a dict lookup, four scalar updates, and
one ``searchsorted`` into the shared edge vector). Call sites open each
phase with :class:`span`, which puts it on two clocks: it always opens a
``jax.profiler.TraceAnnotation`` named ``carbonedge.<phase>``, so a
profiler capture shows the phase beside the device's operations, and only
when a profiler is attached does it read ``perf_counter`` and fold the
duration here. Detached, a span costs one inactive ``TraceMe``
(about 1 us) and no accumulation.

Quantiles inherit the histogram's bucket granularity: ``percentile_s``
returns the *upper edge* of the bin holding the target rank (see the
quantile-granularity contract in ``repro.obs.registry``), so a p50 of
``0.0001`` means the median span fell in the ``(10^-4.5, 10^-4]`` s
bin. Pass custom ``edges`` at construction when half-decade resolution
is too coarse for a phase you care about.
"""
from __future__ import annotations

from functools import lru_cache
from time import perf_counter
from typing import Dict, Optional

import numpy as np

# Span-duration histogram edges (seconds): half-decade steps from 100 ns
# to 10 s, plus an implicit overflow bin. Fixed edges keep summaries
# comparable across phases, runs, and CI artifacts.
SPAN_EDGES_S = 10.0 ** np.arange(-7.0, 1.5, 0.5)

# Prefix of every program span on the profiler's trace.
TRACE_PREFIX = "carbonedge."


class _Phase:
    __slots__ = ("count", "total_s", "min_s", "max_s", "bins")

    def __init__(self, n_bins: int) -> None:
        self.count = 0
        self.total_s = 0.0
        self.min_s = float("inf")
        self.max_s = 0.0
        self.bins = np.zeros(n_bins, dtype=np.int64)


class StepProfiler:
    """Accumulate named wall-clock spans into per-phase histograms.

    ``edges`` (seconds, ascending) overrides the shared half-decade
    :data:`SPAN_EDGES_S` — a caller-supplied resolution choice made at
    construction, because bin counts cannot be re-binned afterwards."""

    def __init__(self, edges=None) -> None:
        self.edges = np.asarray(SPAN_EDGES_S if edges is None else edges,
                                dtype=float)
        if self.edges.ndim != 1 or self.edges.size == 0:
            raise ValueError("edges must be a non-empty 1-D array")
        self._phases: Dict[str, _Phase] = {}

    def add(self, phase: str, dt_s: float) -> None:
        """Fold one span of ``dt_s`` seconds into ``phase``."""
        p = self._phases.get(phase)
        if p is None:
            p = self._phases[phase] = _Phase(self.edges.size + 1)
        p.count += 1
        p.total_s += dt_s
        if dt_s < p.min_s:
            p.min_s = dt_s
        if dt_s > p.max_s:
            p.max_s = dt_s
        p.bins[int(np.searchsorted(self.edges, dt_s, side="right"))] += 1

    def span(self, phase: str) -> "span":
        """Context-manager form of :meth:`add`: ``span(self, phase)``."""
        return span(self, phase)

    def count(self, phase: str) -> int:
        p = self._phases.get(phase)
        return 0 if p is None else p.count

    def total_s(self, phase: str) -> float:
        p = self._phases.get(phase)
        return 0.0 if p is None else p.total_s

    def phases(self):
        return sorted(self._phases)

    def percentile_s(self, phase: str, q: float) -> float:
        """Histogram-resolution upper bound on the ``q`` quantile (q in
        [0, 1]): the upper edge of the bin where the cumulative count
        crosses ``q * count`` (the observed max for the overflow bin)."""
        p = self._phases.get(phase)
        if p is None or p.count == 0:
            return float("nan")
        cum = np.cumsum(p.bins)
        i = int(np.searchsorted(cum, q * p.count, side="left"))
        if i >= self.edges.size:
            return p.max_s
        return float(self.edges[i])

    def summary(self) -> Dict:
        """JSON-ready per-phase aggregates plus the shared bin edges."""
        phases = {}
        for name in sorted(self._phases):
            p = self._phases[name]
            phases[name] = {
                "count": p.count,
                "total_s": p.total_s,
                "mean_s": p.total_s / p.count if p.count else 0.0,
                "min_s": p.min_s if p.count else 0.0,
                "max_s": p.max_s,
                "p50_s": self.percentile_s(name, 0.50),
                "p95_s": self.percentile_s(name, 0.95),
                "hist": p.bins.tolist(),
            }
        return {"edges_s": self.edges.tolist(), "phases": phases}

    def reset(self) -> None:
        self._phases.clear()


@lru_cache(maxsize=None)
def _trace_me():
    """``jax.profiler.TraceAnnotation``, imported on the first span so that
    this package keeps no import of JAX."""
    from jax.profiler import TraceAnnotation
    return TraceAnnotation


class span:
    """One program phase on both clocks: ``with span(prof, "score"): ...``.

    Always a ``TraceAnnotation`` named ``carbonedge.<phase>``, which the
    profiler records only while a capture is running (inactive, about
    1 us). With ``prof`` not ``None`` the ``perf_counter`` duration of a
    phase that completes is also folded into ``prof`` as
    :meth:`StepProfiler.add` does; one that raises is not. It neither
    waits for the device nor reorders the enclosed work."""

    __slots__ = ("prof", "phase", "_me", "_t0")

    def __init__(self, prof: Optional[StepProfiler], phase: str) -> None:
        self.prof = prof
        self.phase = phase
        self._me = _trace_me()(TRACE_PREFIX + phase)
        self._t0 = 0.0

    def __enter__(self) -> "span":
        self._me.__enter__()
        if self.prof is not None:
            self._t0 = perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if self.prof is not None and exc_type is None:
            self.prof.add(self.phase, perf_counter() - self._t0)
        self._me.__exit__(exc_type, exc, tb)
        return False
