"""Incremental feature-state cache (DESIGN.md §3).

``featurize`` (core/policy.py) rebuilds the (B, N, 8) feature tensor with a
Python loop over all N nodes — and one ``provider.intensity`` call per
node — on every engine step. At fleet scale (N >= 10^4) that loop *is* the
scheduling overhead. :class:`FeatureCache` removes it:

- the cluster owns persistent per-node **column arrays** (free cpu/mem,
  load, avg time, running, derived E_est, static intensity);
- every ``NodeState`` field write marks its node dirty (see
  ``NodeState.__setattr__``), so :meth:`sync` refreshes **O(changed)** rows
  — an engine step that executed B tasks re-reads B rows, not N;
- grid intensity is fetched through the **batched provider API**
  (``providers.intensity_batch``: one vectorized call, not N Python calls) and
  memoized per (provider, hour) — a ``TIME_INVARIANT`` provider (e.g.
  ``StaticProvider``) is queried at most once per node, ever;
- only nodes some task in the batch could actually use are queried
  (``need`` mask), preserving ``featurize``'s partial-coverage-provider
  guarantee.

Row refreshes use the *same scalar arithmetic* as ``featurize``'s per-node
loop, so cached columns are bit-identical to a fresh featurize — the fresh
path survives as the parity oracle (tests/test_featcache.py).

Invalidation contract:
- ``NodeState`` field writes        -> automatic (dirty set)
- ``EdgeCluster.add_node/remove_node`` -> automatic (topology rev, rebuild)
- direct ``cluster.nodes[...] =`` surgery, ``host_power_w`` or ``NodeSpec``
  replacement -> caller must call ``cluster.invalidate_features()``
"""
from __future__ import annotations

from typing import List, Optional

import numpy as np

from repro.core.providers import intensity_batch
from repro.core.scheduler import LOAD_THRESHOLD


class FeatureCache:
    """Persistent per-node feature columns for one :class:`EdgeCluster`.

    Obtain via ``cluster.feature_cache()`` (which syncs); do not construct
    one per step.
    """

    def __init__(self, cluster):
        self.cluster = cluster
        # Feature-state revision: bumped only when a refresh actually
        # CHANGES a column value (or the topology rebuilds) — a dirty node
        # whose re-read values are identical leaves it untouched. Consumers
        # (the VectorizedPolicy selection memo, DESIGN.md §6) may reuse any
        # pure function of the columns while data_rev is unchanged.
        self.data_rev = 0
        # Resilience columns (DESIGN.md §10), owned by an attached
        # repro.resilience.FleetHealth: `avail` is the (N,) bool
        # availability mask node_ok() ANDs in, `fail_count` the (N,)
        # cumulative contact-failure counter. Both stay None — literally
        # absent, zero cost, bit-identical — until the health layer has
        # something to say; every mask mutation bumps data_rev.
        self._health = None
        self._rebuild()

    # -- construction / refresh -------------------------------------------
    def _alloc(self, n: int) -> None:
        self.n = n
        for col in ("cpu", "mem_mb", "load", "mem_used", "free_cpu",
                    "free_mem", "avg_time_ms", "avg_time_s", "running",
                    "power", "e_est", "carbon_static"):
            setattr(self, col, np.zeros(n))
        self.avail = None        # (N,) bool mask, or None = all available
        self.fail_count = None   # (N,) cumulative failures, or None

    def _refresh_row(self, i: int, st) -> bool:
        # Scalar per-row math, in exactly featurize's evaluation order, so
        # cached columns bit-match the fresh per-node loop. Returns whether
        # any column value actually changed (ledger-only mutations — e.g. a
        # batch of executions — re-dirty a node without moving its
        # features; those must not bump data_rev).
        spec = st.spec
        p = st.power_w(self.cluster.host_power_w)
        changed = not (self.cpu[i] == spec.cpu
                       and self.mem_mb[i] == spec.mem_mb
                       and self.load[i] == st.load
                       and self.mem_used[i] == st.mem_used_mb
                       and self.avg_time_ms[i] == st.avg_time_ms
                       and self.running[i] == st.running
                       and self.power[i] == p
                       and self.carbon_static[i] == spec.carbon_intensity)
        if not changed:
            return False
        self.cpu[i] = spec.cpu
        self.mem_mb[i] = spec.mem_mb
        self.load[i] = st.load
        self.mem_used[i] = st.mem_used_mb
        self.free_cpu[i] = spec.cpu * (1.0 - st.load)
        self.free_mem[i] = spec.mem_mb - st.mem_used_mb
        self.avg_time_ms[i] = st.avg_time_ms
        self.avg_time_s[i] = st.avg_time_ms / 1000.0
        self.running[i] = st.running
        self.power[i] = p
        self.e_est[i] = p * st.avg_time_ms / 3.6e6
        self.carbon_static[i] = spec.carbon_intensity
        return True

    def _rebuild(self) -> None:
        cl = self.cluster
        self.names: List[str] = list(cl.nodes)
        self.index = {n: i for i, n in enumerate(self.names)}
        self._alloc(len(self.names))
        for i, st in enumerate(cl.nodes.values()):
            # Adopt states inserted by direct cluster.nodes surgery (the
            # invalidate_features() escape hatch): without a dirty sink
            # their future mutations would go untracked.
            if getattr(st, "_dirty_sink", None) is not cl._dirty:
                st._dirty_sink = cl._dirty
            self._refresh_row(i, st)
        cl._dirty.clear()
        self._topo_seen = cl._topo_rev
        self.data_rev += 1
        self._reset_intensity_cache()
        self._part_blocks = {}
        if self._health is not None:
            # re-project the health mask onto the new topology — a rebuild
            # must not silently unmask a blocked node (DESIGN.md §10)
            self._health.push(self)

    def sync(self) -> None:
        """Bring columns up to date: O(changed) row refreshes, or a full
        rebuild when the fleet's membership changed."""
        cl = self.cluster
        if self._topo_seen != cl._topo_rev or self.n != len(cl.nodes):
            self._rebuild()
            return
        if cl._dirty:
            nodes = cl.nodes
            index = self.index
            changed = False
            for name in cl._dirty:
                i = index.get(name)
                if i is None:          # name we never indexed: stale topo
                    self._rebuild()
                    return
                changed |= self._refresh_row(i, nodes[name])
            cl._dirty.clear()
            if changed:
                self.data_rev += 1

    # -- intensity memoization --------------------------------------------
    def _reset_intensity_cache(self) -> None:
        self._int_provider = None
        self._int_hour = None
        self._int_vals = np.zeros(self.n)
        self._int_have = np.zeros(self.n, dtype=bool)

    def intensities(self, provider, now_hour: float,
                    need: Optional[np.ndarray] = None) -> np.ndarray:
        """(N,) per-node grid intensity; entries are valid where ``need``
        (all nodes when None). ``provider=None`` returns the static
        regional column. Nodes already fetched under the current
        (provider, hour) key — or under the provider alone when it declares
        ``TIME_INVARIANT`` — are served from cache; the rest go through one
        ``providers.intensity_batch`` call.
        """
        if provider is None:
            return self.carbon_static
        invariant = getattr(provider, "TIME_INVARIANT", False)
        if provider is not self._int_provider or (
                not invariant and now_hour != self._int_hour):
            self._int_provider = provider
            self._int_vals = np.zeros(self.n)
            self._int_have = np.zeros(self.n, dtype=bool)
        self._int_hour = now_hour
        missing = ~self._int_have if need is None else (need & ~self._int_have)
        if missing.any():
            idx = np.nonzero(missing)[0]
            vals = intensity_batch(provider, [self.names[i] for i in idx],
                                   now_hour)
            self._int_vals[idx] = np.asarray(vals, dtype=float)
            self._int_have[idx] = True
        return self._int_vals

    # -- joint partition columns (repro.partition, DESIGN.md §8) -----------
    # Bound on live per-profile blocks: a deployment schedules a handful of
    # model profiles; past this the keys are churning and the dict is
    # dropped wholesale rather than grown without bound.
    _PART_BLOCK_MAX = 64

    def partition_block(self, key, remote_frac: np.ndarray,
                        comm_s: np.ndarray):
        """(P, N) joint time/energy columns for one cut profile:

        ``t[p, n] = avg_time_s[n] * remote_frac[p] + comm_s[p]`` (seconds)
        ``e[p, n] = power[n] * (t * 1e3) / 3.6e6``        (kWh, Eq. 4)

        Cached per ``key`` (the policy passes its hashable (CutProfile,
        link speed) pair) and recomputed only when ``data_rev`` moves, so
        the joint scorer stays on the incremental O(changed) path — a
        steady fleet pays zero per-step column work regardless of P.
        """
        blk = self._part_blocks.get(key)
        if blk is not None and blk[0] == self.data_rev:
            return blk[1], blk[2]
        if len(self._part_blocks) >= self._PART_BLOCK_MAX:
            self._part_blocks.clear()
        t = (self.avg_time_s[None, :] * np.asarray(remote_frac)[:, None]
             + np.asarray(comm_s)[:, None])
        e = self.power[None, :] * (t * 1000.0) / 3.6e6
        self._part_blocks[key] = (self.data_rev, t, e)
        return t, e

    # -- masks -------------------------------------------------------------
    def node_ok(self, latency_threshold_ms: float = float("inf")) -> np.ndarray:
        """(N,) Algorithm-1 line-3 filter: overload cut-off plus the
        policy's latency threshold, ANDed with the resilience availability
        mask when one is attached (DESIGN.md §10) — so every cached scorer
        path (tensor, column, Pallas, partition) masks down/broken nodes
        vectorized, never by Python filtering."""
        ok = self.load <= LOAD_THRESHOLD
        if latency_threshold_ms != float("inf"):
            ok = ok & (self.avg_time_ms <= latency_threshold_ms)
        if self.avail is not None:
            ok = ok & self.avail
        return ok

    def feasible(self, task_cpu: np.ndarray, task_mem: np.ndarray,
                 latency_threshold_ms: float = float("inf")) -> np.ndarray:
        """(B, N) feasibility for B tasks given as (B,) cpu/mem arrays —
        the vectorized ``node_feasible`` (+ latency filter)."""
        return (self.node_ok(latency_threshold_ms)[None, :]
                & (self.free_cpu[None, :] >= np.asarray(task_cpu)[:, None])
                & (self.free_mem[None, :] >= np.asarray(task_mem)[:, None]))

    def usable(self, task_cpu: np.ndarray, task_mem: np.ndarray,
               ok: np.ndarray) -> np.ndarray:
        """(N,) nodes that some task fits, given the ``node_ok`` mask:
        ``feasible(...).any(axis=0)`` in O((B + N) log B), without the
        (B, N) array. With the tasks sorted by cpu, a node holds the cpu of
        a prefix of them, and fits one iff the prefix's least memory fits;
        every compare is the float64 one ``feasible`` makes."""
        order = np.argsort(task_cpu, kind="stable")
        cpu = np.asarray(task_cpu, np.float64)[order]
        least_mem = np.minimum.accumulate(np.asarray(task_mem,
                                                     np.float64)[order])
        k = np.searchsorted(cpu, self.free_cpu, side="right")
        fits = k > 0
        fits[fits] = least_mem[k[fits] - 1] <= self.free_mem[fits]
        return ok & fits
