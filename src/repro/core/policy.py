"""Scheduling policies (DESIGN.md §1.2): one scoring rule, three engines.

``featurize`` is the single source of the (N, 8) feature-matrix layout the
numpy scorer, the scalar oracle and the Pallas ``node_scores`` kernel all
share — the paper's Eq. 3/4 components are computed from these columns and
nowhere else:

  0 cpu_free_frac   free_cpu / task.cpu        (min(.,1) -> half of S_R)
  1 mem_free_frac   free_mem / task.mem_mb     (min(.,1) -> half of S_R)
  2 load            -> S_L = 1 - load
  3 avg_time_s      -> S_P = 1 / (1 + t)
  4 running         -> S_B = 1 / (1 + 2r)
  5 intensity*E_est -> S_C = 1 / (1 + I*E)     (Eq. 4)
  6 valid           feasibility filter (Algorithm 1 lines 3-5)
  7 padding

:func:`featurize_columns` hands the Pallas column kernel the same values
as node columns and task profiles, for the kernel to form each cell on the
chip.

Policies:

- :class:`WeightedScoringPolicy` — the scalar Python loop (Algorithm 1
  verbatim). Survives as the parity oracle.
- :class:`VectorizedPolicy` — batched (B, N) selection in one call, with
  one scorer per backend: the float64 numpy tensor path, and on TPU the
  Pallas column kernel ``select_best_columns`` over the cluster's
  :class:`~repro.core.featcache.FeatureCache`. The engine default.
- :class:`TemporalPolicy` — deferral as a (slot x node) grid where the
  Eq. 4 column is time-indexed through the intensity provider; min-carbon
  placement with the weighted score as tie-breaker.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional, Sequence, Tuple

import numpy as np

from repro.core.cluster import EdgeCluster
from repro.core.providers import (CarbonIntensityProvider, StaticProvider,
                                  intensity_batch)
from repro.core.scheduler import (LOAD_THRESHOLD, Task, Weights,
                                  node_feasible, scores, vector_scores)
from repro.obs.profiler import span

# Scores below this are "invalid" sentinels (the Pallas kernel emits -1e30,
# the numpy path -inf).
_NEG_SENTINEL = -1e29

FEATURE_DIM = 8
(COL_CPU_FREE, COL_MEM_FREE, COL_LOAD, COL_TIME_S,
 COL_RUNNING, COL_IXE, COL_VALID, COL_PAD) = range(FEATURE_DIM)


def featurize(cluster: EdgeCluster, tasks: Sequence[Task],
              provider: Optional[CarbonIntensityProvider] = None,
              now_hour: float = 0.0,
              latency_threshold_ms: float = 5000.0,
              dtype=np.float64) -> Tuple[np.ndarray, List[str]]:
    """Extract the (B, N, 8) feature tensor for B tasks against N nodes.

    Grid intensity is read exclusively through ``provider`` (defaulting to
    the cluster's static regional values). Returns (features, node_names)
    with node order matching the cluster's insertion order, so an argmax
    over scores indexes ``node_names`` directly.
    """
    names = list(cluster.nodes)
    B, N = len(tasks), len(names)
    # Only the resource columns depend on the task, so the task dimension is
    # pure numpy broadcasting — the Python cost of a batched step is O(N+B),
    # not O(N*B).
    task_cpu = np.array([t.cpu for t in tasks], dtype)
    task_mem = np.array([t.mem_mb for t in tasks], dtype)
    F = np.zeros((B, N, FEATURE_DIM), dtype)
    for j, name in enumerate(names):
        st = cluster.nodes[name]
        free_cpu = st.spec.cpu * (1.0 - st.load)
        free_mem = st.spec.mem_mb - st.mem_used_mb
        node_ok = (st.load <= LOAD_THRESHOLD
                   and st.avg_time_ms <= latency_threshold_ms)
        feasible = node_ok & (free_cpu >= task_cpu) & (free_mem >= task_mem)
        # Query the provider only when some task can actually use the node
        # (like the scalar oracle, which filters before reading intensity):
        # a masked column's Eq. 4 value is irrelevant, and a
        # partial-coverage provider must not fail on unusable nodes.
        # No provider => the node's static regional value, without building
        # a throwaway StaticProvider per call (this is the hot path).
        if not feasible.any():
            intensity = 0.0
        elif provider is not None:
            intensity = provider.intensity(name, now_hour)
        else:
            intensity = st.spec.carbon_intensity
        e_est = st.power_w(cluster.host_power_w) * st.avg_time_ms / 3.6e6
        cpu_frac = np.ones(B, dtype)
        np.divide(free_cpu, task_cpu, out=cpu_frac, where=task_cpu > 0)
        mem_frac = np.ones(B, dtype)
        np.divide(free_mem, task_mem, out=mem_frac, where=task_mem > 0)
        F[:, j, COL_CPU_FREE] = cpu_frac
        F[:, j, COL_MEM_FREE] = mem_frac
        F[:, j, COL_LOAD] = st.load
        F[:, j, COL_TIME_S] = st.avg_time_ms / 1000.0
        F[:, j, COL_RUNNING] = st.running
        # masked entries carry 0, keeping each batch row independent of its
        # batch-mates (a row equals featurizing that task alone)
        F[:, j, COL_IXE] = np.where(feasible, intensity * e_est, 0.0)
        F[:, j, COL_VALID] = feasible.astype(dtype)
    return F, names


def featurize_cached(cache, tasks: Sequence[Task],
                     provider: Optional[CarbonIntensityProvider] = None,
                     now_hour: float = 0.0,
                     latency_threshold_ms: float = 5000.0,
                     dtype=np.float64) -> Tuple[np.ndarray, List[str]]:
    """(B, N, 8) feature tensor from a synced
    :class:`~repro.core.featcache.FeatureCache` — same layout and *bit-
    identical values* as :func:`featurize`, without the per-node Python
    loop or the N per-node provider calls (grid intensity is one batched
    read, memoized per (provider, hour), and only feasible nodes are
    queried — the partial-coverage guarantee carries over).
    """
    B, N = len(tasks), cache.n
    task_cpu = np.array([t.cpu for t in tasks], dtype)
    task_mem = np.array([t.mem_mb for t in tasks], dtype)
    F = np.zeros((B, N, FEATURE_DIM), dtype)
    feasible = cache.feasible(task_cpu, task_mem, latency_threshold_ms)
    ints = cache.intensities(provider, now_hour, need=feasible.any(axis=0))
    cpu_frac = np.ones((B, N), dtype)
    np.divide(cache.free_cpu[None, :], task_cpu[:, None], out=cpu_frac,
              where=(task_cpu > 0)[:, None])
    mem_frac = np.ones((B, N), dtype)
    np.divide(cache.free_mem[None, :], task_mem[:, None], out=mem_frac,
              where=(task_mem > 0)[:, None])
    F[:, :, COL_CPU_FREE] = cpu_frac
    F[:, :, COL_MEM_FREE] = mem_frac
    F[:, :, COL_LOAD] = cache.load[None, :]
    F[:, :, COL_TIME_S] = cache.avg_time_s[None, :]
    F[:, :, COL_RUNNING] = cache.running[None, :]
    F[:, :, COL_IXE] = np.where(feasible, (ints * cache.e_est)[None, :], 0.0)
    F[:, :, COL_VALID] = feasible.astype(dtype)
    return F, list(cache.names)


def featurize_columns(cache, tasks: Sequence[Task],
                      provider: Optional[CarbonIntensityProvider] = None,
                      now_hour: float = 0.0,
                      latency_threshold_ms: float = 5000.0):
    """The column select kernel's operands (``kernels/node_score.py``) from
    a synced :class:`~repro.core.featcache.FeatureCache`, in O(B + N)
    where :func:`featurize_cached` is O(B x N): (7, N) float32 node rows —
    free cpu, free memory, load, avg time, running, I x E_est, node_ok —
    their (4, N) int32 float64 keys of free cpu and memory, the (B, 2)
    float32 task profiles and their (B, 4) keys.

    Each scored value is the f32 cast of the float64 value
    ``featurize_cached`` puts in its tensor, and feasibility is left to the
    kernel's exact compare of the keys. Grid intensity is read for the
    nodes some task can use (``FeatureCache.usable``), the same set that
    ``featurize_cached`` queries, so the partial-coverage guarantee
    carries over.
    """
    from repro.kernels.node_score import f64_keys

    task_cpu = np.array([t.cpu for t in tasks], dtype=float)
    task_mem = np.array([t.mem_mb for t in tasks], dtype=float)
    ok = cache.node_ok(latency_threshold_ms)
    ints = cache.intensities(provider, now_hour,
                             need=cache.usable(task_cpu, task_mem, ok))
    nodes = np.stack([cache.free_cpu, cache.free_mem, cache.load,
                      cache.avg_time_s, cache.running, ints * cache.e_est,
                      ok]).astype(np.float32)
    node_keys = np.concatenate([f64_keys(cache.free_cpu),
                                f64_keys(cache.free_mem)])
    task_rows = np.stack([task_cpu, task_mem], axis=1).astype(np.float32)
    task_keys = np.concatenate([f64_keys(task_cpu), f64_keys(task_mem)]).T
    return nodes, node_keys, task_rows, task_keys


def get_cache(cluster):
    """The cluster's synced FeatureCache, or None for cluster-likes that
    don't carry one (anything without the EdgeCluster topology plumbing).
    Shared by the policies here and :class:`repro.partition.policy.
    PartitionPolicy` (which widens selection to (B, P, N))."""
    fc = getattr(cluster, "feature_cache", None)
    return fc() if callable(fc) else None


class _SelectionMemo:
    """Profile-level selection memo over unchanged feature state
    (DESIGN.md §6).

    Selection is a pure function of (cache columns, provider, hour,
    weights, backend, latency threshold, task (cpu, mem_mb) profile) —
    batch rows are independent of their batch-mates. The cache's
    ``data_rev`` only moves when a column VALUE changes (execution-ledger
    writes re-dirty nodes without moving features), so in steady state a
    repeated request profile resolves to a dict hit instead of an (N,)
    scoring pass. Any epoch drift — feature change, different provider
    object, new hour on a time-varying provider — drops the whole memo.
    Stored on the FeatureCache (``cache._sel_memo``) so it lives and dies
    with the cluster it describes.
    """

    __slots__ = ("rev", "provider", "hour", "map")

    def __init__(self):
        self.rev = None
        self.provider = None
        self.hour = None
        self.map: dict = {}

    def sync_epoch(self, cache, provider, now_hour: float) -> None:
        # A TIME_INVARIANT (or absent) provider answers identically for
        # every hour, so the hour is not part of its epoch.
        hour = (None if provider is None
                or getattr(provider, "TIME_INVARIANT", False) else now_hour)
        if (self.rev != cache.data_rev or self.provider is not provider
                or self.hour != hour):
            self.rev = cache.data_rev
            self.provider = provider
            self.hour = hour
            self.map.clear()


# ---------------------------------------------------------------------------
# Scalar oracle (Algorithm 1 verbatim)
# ---------------------------------------------------------------------------


class WeightedScoringPolicy:
    """Python-loop NSA (paper Algorithm 1) — the parity oracle.

    Identical math to the seed's ``select_node``, with intensity read
    through the provider instead of ``NodeSpec.carbon_intensity``.
    """

    name = "scalar"

    def __init__(self, latency_threshold_ms: float = 5000.0):
        self.latency_threshold_ms = latency_threshold_ms

    def select(self, cluster: EdgeCluster, task: Task, weights: Weights,
               provider: Optional[CarbonIntensityProvider] = None,
               now_hour: float = 0.0) -> Optional[str]:
        best_score, best = 0.0, None
        for name, st in cluster.nodes.items():
            if st.avg_time_ms > self.latency_threshold_ms:
                continue
            if not node_feasible(st, task):
                continue
            comp = scores(st, task, cluster.host_power_w,
                          intensity=provider.intensity(name, now_hour)
                          if provider is not None else None)
            s = float(weights.as_array() @ comp)
            if s > best_score:
                best_score, best = s, name
        return best

    def select_batch(self, cluster, tasks, weights, provider=None,
                     now_hour: float = 0.0) -> List[Optional[str]]:
        return [self.select(cluster, t, weights, provider, now_hour)
                for t in tasks]


# ---------------------------------------------------------------------------
# Vectorized / Pallas policy (engine default)
# ---------------------------------------------------------------------------


class VectorizedPolicy:
    """Batched NSA: one scorer call for B tasks x N nodes.

    ``backend``:
      - ``"auto"``   — Pallas kernel on TPU, numpy elsewhere (default);
      - ``"numpy"``  — float64 numpy (bit-matches the scalar oracle);
      - ``"pallas"`` — the column kernel ``select_best_columns`` (interpret
        mode off TPU), float32 scores with exact float64 feasibility.

    One scorer per backend (DESIGN.md §3). Numpy builds the (B, N, 8)
    float64 tensor — ``featurize_cached`` from the cluster's incremental
    :class:`~repro.core.featcache.FeatureCache` in ``_CHUNK_ELEMS``
    chunks of the task axis — and scores it in
    :meth:`_select_from_features`. Pallas ships node columns and task
    profiles (:func:`featurize_columns`), padded to power-of-two buckets
    so distinct (B, N) stop retriggering jit, and the kernel scores every
    (task, node) cell on the chip in one launch per step. A cluster-like
    without a FeatureCache is scored by the float64 reference
    (``featurize`` + :meth:`_select_from_features`) on either backend.
    Duplicate task resource profiles share one scored row.
    """

    name = "vectorized"

    # Bound on elements per (chunk x nodes) scoring block of the numpy
    # paths: ~64 MB of f64 features per chunk at FEATURE_DIM=8.
    _CHUNK_ELEMS = 1 << 20

    # Per-config selection-memo size bound: a request mix has a handful of
    # live (cpu, mem_mb) profiles; past this many the keys are effectively
    # continuous and the memo is dropped rather than grown without bound.
    MEMO_MAX_PROFILES = 4096

    def __init__(self, backend: str = "auto",
                 latency_threshold_ms: float = 5000.0,
                 use_select_memo: bool = True):
        if backend not in ("auto", "numpy", "pallas"):
            raise ValueError(f"unknown backend {backend!r}")
        self.backend = backend
        self.latency_threshold_ms = latency_threshold_ms
        # Steady-state fast path (DESIGN.md §6): memoize per-profile
        # selection while the cache's data_rev / provider / hour epoch
        # holds. False forces a fresh scoring pass every call — what the
        # fleet-scale featurize benchmarks measure.
        self.use_select_memo = use_select_memo
        # Observability hooks (DESIGN.md §9), both no-ops by default: a
        # repro.obs StepProfiler on `profiler` gets featurize/score span
        # timings; `capture_scores = True` additionally publishes the
        # winning and runner-up totals of the last select_batch on
        # `last_scores` ({"score": (B,), "runner_up": (B,)}) without
        # perturbing any choice.
        self.profiler = None
        self.capture_scores = False
        self.last_scores = None

    def _resolved_backend(self) -> str:
        if self.backend != "auto":
            return self.backend
        import jax
        return "pallas" if jax.default_backend() == "tpu" else "numpy"

    # -- scoring -----------------------------------------------------------
    def score_batch(self, features: np.ndarray, weights: Weights) -> np.ndarray:
        """(B, N, 8) features -> (B, N) total scores; invalid rows get the
        negative sentinel. One kernel launch on the pallas backend."""
        w5 = weights.as_array()
        if self._resolved_backend() == "pallas":
            return self._score_pallas(features, w5)
        return self._score_numpy(features, w5)

    @staticmethod
    def _score_numpy(F: np.ndarray, w5: np.ndarray) -> np.ndarray:
        flat = F.reshape(-1, FEATURE_DIM)
        total = vector_scores(flat[:, :6], w5)
        total = np.where(flat[:, COL_VALID] > 0.5, total, -np.inf)
        return total.reshape(F.shape[0], F.shape[1])

    @staticmethod
    def _bucket(n: int, floor: int = 8) -> int:
        """Next power-of-two shape bucket: padding (B, N) to buckets keeps
        the jit/Mosaic compile count logarithmic in fleet size instead of
        one compile per distinct shape."""
        b = floor
        while b < n:
            b <<= 1
        return b

    @classmethod
    def _pad_to_buckets(cls, F: np.ndarray) -> np.ndarray:
        B, N = F.shape[:2]
        Bp, Np = cls._bucket(B), cls._bucket(N)
        if (Bp, Np) == (B, N):
            return np.asarray(F, np.float32)
        Fp = np.zeros((Bp, Np, FEATURE_DIM), np.float32)
        Fp[:B, :N] = F                 # pad rows: valid=0 -> masked out
        return Fp

    def _score_pallas(self, F: np.ndarray, w5: np.ndarray) -> np.ndarray:
        import jax.numpy as jnp

        from repro.kernels import ops

        B, N = F.shape[:2]
        w8 = np.zeros(FEATURE_DIM, np.float32)
        w8[:5] = w5
        out = ops.node_scores_batched(jnp.asarray(self._pad_to_buckets(F)),
                                      jnp.asarray(w8))
        return np.asarray(out, np.float64)[:B, :N]

    def _select_from_features(self, F: np.ndarray, names: List[str],
                              weights: Weights) -> List[Optional[str]]:
        """The float64 scorer: (B, N, 8) features -> B winners (numpy on
        every backend). Algorithm 1 requires a strictly positive score
        (best_score init 0)."""
        totals = self._score_numpy(F, weights.as_array())
        best = np.argmax(totals, axis=1)
        if self.capture_scores:
            self._cap_block(totals, best)
        return [names[b] if totals[i, b] > 0.0 else None
                for i, b in enumerate(best)]

    # -- score capture (repro.obs decision tracing) ------------------------
    def _cap_block(self, totals: np.ndarray, best: np.ndarray) -> None:
        """Stash the winning and runner-up totals of one scored (U, N)
        block. Runner-up = max over the row with the winner cell masked
        (-inf when N < 2), computed on a copy so selection is untouched."""
        U, N = totals.shape
        rows = np.arange(U)
        self._cap_s.append(totals[rows, best])
        if N < 2:
            self._cap_r.append(np.full(U, -np.inf))
            return
        masked = totals.copy()
        masked[rows, best] = -np.inf
        self._cap_r.append(masked.max(axis=1))

    def _cap_finalize(self) -> None:
        """Rep-level capture arrays for the just-scored blocks, in rep
        order (select_batch expands them to task order)."""
        self._cap = {
            "score": (np.concatenate(self._cap_s) if self._cap_s
                      else np.zeros(0)),
            "runner_up": (np.concatenate(self._cap_r) if self._cap_r
                          else np.zeros(0)),
        }

    # -- selection ---------------------------------------------------------
    def select_batch(self, cluster: EdgeCluster, tasks: Sequence[Task],
                     weights: Weights,
                     provider: Optional[CarbonIntensityProvider] = None,
                     now_hour: float = 0.0) -> List[Optional[str]]:
        if not tasks:
            return []
        # Dedupe task resource profiles: the feature rows (and therefore
        # the selection) depend only on (cpu, mem_mb), and batch rows are
        # independent of their batch-mates — B identical tasks cost one
        # scored row, not B.
        keys = [(t.cpu, t.mem_mb) for t in tasks]
        uniq: dict = {}
        reps: List[Task] = []
        for t, key in zip(tasks, keys):
            if key not in uniq:
                uniq[key] = len(reps)
                reps.append(t)
        chosen = self._select_unique(cluster, reps, weights, provider,
                                     now_hour)
        if not self.capture_scores:
            return [chosen[uniq[key]] for key in keys]
        # expand rep-level capture to task order with the same index map;
        # fromiter over map(dict.__getitem__) builds the index at C speed
        # and the object-array gather + tolist replaces the off path's
        # per-task dict-lookup listcomp — capture costs ~the off path
        idx = np.fromiter(map(uniq.__getitem__, keys), np.intp,
                          count=len(keys))
        self.last_scores = {k: np.asarray(v)[idx]
                            for k, v in self._cap.items()}
        return np.asarray(chosen, dtype=object)[idx].tolist()

    def _select_unique(self, cluster, reps: Sequence[Task], weights: Weights,
                       provider, now_hour: float) -> List[Optional[str]]:
        cap = self.capture_scores
        if cap:
            self._cap_s, self._cap_r = [], []
            self.last_scores = None
        cache = get_cache(cluster)
        if cache is None:
            prof = self.profiler
            with span(prof, "featurize"):
                F, names = featurize(cluster, reps, provider, now_hour,
                                     self.latency_threshold_ms)
            with span(prof, "score"):
                out = self._select_from_features(F, names, weights)
            if cap:
                self._cap_finalize()
            return out
        if not self.use_select_memo:
            out = self._select_cached(cache, reps, weights, provider,
                                      now_hour)
            if cap:
                self._cap_finalize()
            return out
        memo = getattr(cache, "_sel_memo", None)
        if memo is None:
            memo = cache._sel_memo = _SelectionMemo()
        memo.sync_epoch(cache, provider, now_hour)
        # `cap` is part of the key: capture-on tables store
        # (choice, score, runner_up) triples, plain tables bare choices
        cfg = (self._resolved_backend(), self.latency_threshold_ms,
               weights.as_array().tobytes(), cap)
        table = memo.map.setdefault(cfg, {})   # hash cfg once, not per key
        keys = [(t.cpu, t.mem_mb) for t in reps]
        missing = [i for i, k in enumerate(keys) if k not in table]
        if missing:
            chosen = self._select_cached(cache, [reps[i] for i in missing],
                                         weights, provider, now_hour)
            if len(table) + len(missing) > self.MEMO_MAX_PROFILES:
                # Continuous-valued profiles never repeat: without a bound
                # a long-lived engine would grow the table one dead entry
                # per task. Dropping it wholesale is cheap — a workload
                # with that many live profiles gets no hits anyway.
                table.clear()
            if cap:
                ms = np.concatenate(self._cap_s) if self._cap_s \
                    else np.zeros(0)
                mr = np.concatenate(self._cap_r) if self._cap_r \
                    else np.zeros(0)
                for j, (i, ch) in enumerate(zip(missing, chosen)):
                    table[keys[i]] = (ch, float(ms[j]), float(mr[j]))
            else:
                for i, ch in zip(missing, chosen):
                    table[keys[i]] = ch
        if not cap:
            return [table[k] for k in keys]
        entries = [table[k] for k in keys]
        self._cap = {
            "score": np.array([e[1] for e in entries]),
            "runner_up": np.array([e[2] for e in entries]),
        }
        return [e[0] for e in entries]

    def _select_cached(self, cache, reps: Sequence[Task], weights: Weights,
                       provider, now_hour: float) -> List[Optional[str]]:
        """One fresh scoring pass over the synced cache columns (no memo)."""
        if self._resolved_backend() == "pallas":
            return self._select_cached_pallas(cache, reps, weights, provider,
                                              now_hour)
        names = cache.names
        chunk = max(1, self._CHUNK_ELEMS // max(cache.n, 1))
        prof = self.profiler
        out: List[Optional[str]] = []
        for lo in range(0, len(reps), chunk):
            with span(prof, "featurize"):
                F, _ = featurize_cached(cache, reps[lo:lo + chunk], provider,
                                        now_hour, self.latency_threshold_ms)
            with span(prof, "score"):
                out.extend(self._select_from_features(F, names, weights))
        return out

    @classmethod
    def _pad_columns(cls, nodes: np.ndarray, node_keys: np.ndarray,
                     tasks: np.ndarray, task_keys: np.ndarray):
        """:func:`featurize_columns`' operands padded to power-of-two
        (U, N) buckets: padding nodes are not ok, so they score NEG_INF."""
        def pad(a, shape):              # np.pad costs 3x this per step
            out = np.zeros(shape, a.dtype)
            out[:a.shape[0], :a.shape[1]] = a
            return out

        Up, Np = cls._bucket(tasks.shape[0]), cls._bucket(nodes.shape[1])
        return (pad(nodes, (nodes.shape[0], Np)), pad(node_keys, (4, Np)),
                pad(tasks, (Up, 2)), pad(task_keys, (Up, 4)))

    def _select_cached_pallas(self, cache, reps: Sequence[Task],
                              weights: Weights, provider,
                              now_hour: float) -> List[Optional[str]]:
        """Pallas selection straight from the cache's node columns: the
        host assembles O(U + N) columns (:func:`featurize_columns`) and the
        column kernel scores every (task, node) cell on the chip, all U
        rows in one launch."""
        import jax

        from repro.kernels import ops

        prof = self.profiler
        with span(prof, "featurize"):
            cols = featurize_columns(cache, reps, provider, now_hour,
                                     self.latency_threshold_ms)
            w8 = np.zeros(FEATURE_DIM, np.float32)
            w8[:5] = weights.as_array()
        with span(prof, "score"), span(prof, "select.columns"):
            with span(prof, "select.pad"):
                padded = self._pad_columns(*cols)
            with span(prof, "select.put"):
                args = jax.device_put((*padded, w8))
            with span(prof, "select.launch"):
                idx, val = ops.select_best_node_columns(*args)
            with span(prof, "select.fetch"):
                idx, val = jax.device_get((idx, val))
                U = len(reps)
                idx, val = idx[:U], np.asarray(val[:U], np.float64)
        if self.capture_scores:
            # winner-only kernel: runner-up not materialized
            self._cap_s.append(val)
            self._cap_r.append(np.full(U, np.nan))
        # Algorithm 1 requires a strictly positive score (best_score init 0)
        names = cache.names
        return [names[b] if v > 0.0 else None for b, v in zip(idx, val)]

    # Below this fleet size a single-task selection is cheaper through the
    # scalar loop than through featurize + array machinery (measured ~11 us
    # vs ~57 us at N=3); the scalar loop and the numpy backend are
    # float64-identical (parity-tested), so "auto" falls through — but only
    # when it resolves to numpy, so that on TPU select() and select_batch()
    # share the float32 kernel path and cannot split near-ties differently.
    SMALL_FLEET_CUTOFF = 64

    def select(self, cluster, task, weights, provider=None,
               now_hour: float = 0.0) -> Optional[str]:
        if (self.backend == "auto"
                and len(cluster.nodes) <= self.SMALL_FLEET_CUTOFF
                and self._resolved_backend() == "numpy"):
            return WeightedScoringPolicy(self.latency_threshold_ms).select(
                cluster, task, weights, provider, now_hour)
        return self.select_batch(cluster, [task], weights, provider,
                                 now_hour)[0]


# ---------------------------------------------------------------------------
# Temporal policy (deferral over a slot grid)
# ---------------------------------------------------------------------------


@dataclass
class Placement:
    node: str
    start_hour: float
    expected_carbon_g: float
    deferred_hours: float


class TemporalPolicy:
    """Space-time NSA: Algorithm 1 over a (start-slot x node) grid.

    The Eq. 4 column becomes time-indexed — column 5 of the shared feature
    layout is rewritten per slot with ``provider.intensity(node, t_slot)``
    — and the whole grid is scored in one ``VectorizedPolicy`` call.
    Placement minimises expected carbon; exact carbon ties are broken by
    the weighted Eq. 3 score (with a tiny deferral penalty so full ties
    stay at "run now").

    The seed's scheduler had no latency-threshold filter on the temporal
    path, so the default threshold here is +inf for behavioural parity.
    """

    name = "temporal"

    def __init__(self, slot_hours: float = 0.5,
                 scorer: Optional[VectorizedPolicy] = None,
                 latency_threshold_ms: Optional[float] = None,
                 backend: str = "auto"):
        """Prefer ``backend=`` to force a scorer backend. If a prebuilt
        ``scorer`` is supplied its latency threshold governs — passing a
        conflicting explicit ``latency_threshold_ms`` raises, mirroring
        TemporalScheduler's slot_hours conflict check."""
        self.slot_hours = slot_hours
        if scorer is not None:
            if (latency_threshold_ms is not None
                    and latency_threshold_ms != scorer.latency_threshold_ms):
                raise ValueError(
                    f"conflicting latency_threshold_ms: {latency_threshold_ms}"
                    f" vs the supplied scorer's {scorer.latency_threshold_ms}")
            if backend != "auto" and backend != scorer.backend:
                raise ValueError(
                    f"conflicting backend: {backend!r} vs the supplied "
                    f"scorer's {scorer.backend!r}")
            self.scorer = scorer
        else:
            self.scorer = VectorizedPolicy(
                backend=backend,
                latency_threshold_ms=(float("inf")
                                      if latency_threshold_ms is None
                                      else latency_threshold_ms))

    def place(self, cluster: EdgeCluster, task, weights: Weights,
              provider: CarbonIntensityProvider,
              now_hour: float = 0.0) -> Optional[Placement]:
        """``task`` needs ``deadline_hours``/``duration_hours`` on top of the
        base Task fields (see temporal.DeferrableTask); a plain Task is
        treated as urgent (run now, zero-duration energy estimate)."""
        deadline = getattr(task, "deadline_hours", 0.0)
        duration = getattr(task, "duration_hours", 0.0)
        horizon = max(deadline - duration, 0.0)
        n_slots = max(1, int(horizon / self.slot_hours) + 1)
        # For deferrable tasks the Eq. 4 column is rebuilt per slot below,
        # so skip the N provider queries featurize would otherwise spend on
        # a column that gets overwritten.
        slot_provider = None if duration > 0 else provider
        cache = get_cache(cluster)
        if cache is not None:
            F, names = featurize_cached(cache, [task], slot_provider,
                                        now_hour,
                                        self.scorer.latency_threshold_ms)
        else:
            F, names = featurize(cluster, [task], slot_provider, now_hour,
                                 self.scorer.latency_threshold_ms)
        G = np.repeat(F, n_slots, axis=0)                     # (S, N, 8)
        # per-node task energy (kWh) at its derived power draw
        if cache is not None:
            e_kwh = cache.power * duration / 1000.0
        else:
            e_kwh = np.array([cluster.nodes[n].power_w(cluster.host_power_w)
                              * duration / 1000.0 for n in names])
        t0 = now_hour + np.arange(n_slots) * self.slot_hours
        mid = t0 + duration / 2.0
        # Slot-grid intensities only for feasible nodes — masked columns
        # stay 0, a partial-coverage provider must not fail on nodes that
        # can never be selected (same guarantee featurize gives the
        # instantaneous policies) — and only when the task has a duration:
        # at duration == 0 the carbon grid is identically zero and the
        # featurize column already holds the Eq. 4 signal.
        feasible = F[0, :, COL_VALID] > 0.5
        grid = np.zeros((n_slots, len(names)))                # (S, N)
        if duration > 0:
            idx = np.nonzero(feasible)[0]
            if idx.size:
                # the whole (S, N_feasible) slot grid in one batched read
                grid[:, idx] = np.asarray(
                    intensity_batch(provider, [names[j] for j in idx], mid)
                ).reshape(n_slots, idx.size)
            G[:, :, COL_IXE] = grid * e_kwh[None, :] * 1e3    # time-indexed S_C
        # duration == 0 (plain/urgent task): keep featurize's e_est-based
        # Eq. 4 column so the carbon weight still differentiates nodes; the
        # zero carbon grid below then ties everywhere and the weighted
        # score picks the winner, matching the instantaneous NSA.
        totals = self.scorer.score_batch(G, weights)          # (S, N)
        valid = totals > _NEG_SENTINEL
        if not valid.any():
            return None
        carbon = grid * e_kwh[None, :]                        # expected gCO2
        masked = np.where(valid, carbon, np.inf)
        tie = masked <= masked.min() + 1e-12
        penalty = (np.arange(n_slots) * 1e-6)[:, None]        # prefer run-now
        cand = np.where(tie, totals - penalty, -np.inf)
        s_idx, n_idx = np.unravel_index(int(np.argmax(cand)), cand.shape)
        return Placement(names[n_idx], float(t0[s_idx]),
                         float(carbon[s_idx, n_idx]),
                         s_idx * self.slot_hours)

    # SchedulingPolicy interface: instantaneous fallback for urgent tasks.
    def select(self, cluster, task, weights, provider=None,
               now_hour: float = 0.0) -> Optional[str]:
        pl = self.place(cluster, task,
                        weights,
                        provider or StaticProvider.from_cluster(cluster),
                        now_hour)
        return pl.node if pl is not None else None

    def select_batch(self, cluster, tasks, weights, provider=None,
                     now_hour: float = 0.0) -> List[Optional[str]]:
        return [self.select(cluster, t, weights, provider, now_hour)
                for t in tasks]
