"""Incremental FeatureCache vs fresh ``featurize`` — the parity oracle
contract (DESIGN.md §3): after any supported mutation sequence (placement,
completion, direct NodeState writes, topology changes, defer/requeue
through the engine) the cached columns must reproduce a fresh featurize
bit-for-bit, including partial-coverage provider masking."""
import numpy as np
import pytest

from repro.core.api import CarbonEdgeEngine
from repro.core.cluster import EdgeCluster, NodeSpec, PAPER_NODES
from repro.core.policy import (VectorizedPolicy, WeightedScoringPolicy,
                               featurize, featurize_cached,
                               featurize_columns)
from repro.core.providers import (FallbackProvider, StaticProvider,
                                  TraceProvider)
from repro.core.scheduler import MODES, Task
from repro.core.temporal import synthetic_trace

from tests.test_policy_parity import random_cluster, random_task


def assert_cache_parity(cluster, tasks, provider=None, now_hour=0.0,
                        thr=5000.0):
    F, names = featurize(cluster, tasks, provider, now_hour, thr)
    Fc, names_c = featurize_cached(cluster.feature_cache(), tasks, provider,
                                   now_hour, thr)
    assert names == names_c
    np.testing.assert_array_equal(F, Fc)


def test_fresh_build_matches_featurize():
    rng = np.random.default_rng(0)
    c = random_cluster(rng, 32)
    tasks = [random_task(rng) for _ in range(5)]
    assert_cache_parity(c, tasks)
    assert_cache_parity(c, tasks, StaticProvider.from_cluster(c), 3.0)


@pytest.mark.parametrize("seed", range(5))
def test_parity_after_randomized_mutation_sequences(seed):
    """Placements/completions (engine.step), direct NodeState pokes, and
    profile() interleave; the cache must track every one O(changed)."""
    rng = np.random.default_rng(seed)
    c = random_cluster(rng, int(rng.integers(4, 24)))
    provider = StaticProvider.from_cluster(c)
    eng = CarbonEdgeEngine(c, mode="green", provider=provider)
    for step in range(12):
        op = rng.integers(0, 4)
        if op == 0:                       # placement/completion via engine
            eng.submit_many([Task(cpu=0.01, mem_mb=1.0)
                             for _ in range(int(rng.integers(1, 4)))])
            try:
                eng.step(now_hour=float(step))
            except RuntimeError:
                pass                      # infeasible: requeued, still a mutation
        elif op == 1:                     # direct state writes
            name = list(c.nodes)[int(rng.integers(0, len(c.nodes)))]
            st = c.nodes[name]
            st.load = float(rng.uniform(0.0, 1.0))
            st.mem_used_mb = float(rng.uniform(0.0, st.spec.mem_mb))
            st.running = int(rng.integers(0, 5))
        elif op == 2:                     # re-profile the whole fleet
            c.profile(float(rng.uniform(50.0, 800.0)))
        else:                             # defer/requeue-like queue churn
            eng.submit(Task(cpu=1e9))     # infeasible
            with pytest.raises(RuntimeError):
                eng.step(now_hour=float(step))
            eng.queue.clear()
        tasks = [random_task(rng) for _ in range(int(rng.integers(1, 5)))]
        assert_cache_parity(c, tasks, provider, now_hour=float(step))


def test_parity_with_partial_coverage_provider():
    """A provider covering only feasible nodes must not be queried for
    masked ones — and the cached path must match featurize exactly."""
    rng = np.random.default_rng(42)
    c = random_cluster(rng, 12)
    task = random_task(rng)
    # overload half the fleet, register intensities only for the rest
    names = list(c.nodes)
    for name in names[::2]:
        c.nodes[name].load = 0.95
    feasible_names = [n for n in names
                      if c.nodes[n].load <= 0.8]
    provider = StaticProvider({n: 500.0 for n in feasible_names})
    assert_cache_parity(c, [task], provider)
    featurize_columns(c.feature_cache(), [task], provider)


@pytest.mark.parametrize("featurizer", [featurize_cached, featurize_columns])
def test_partial_coverage_uncovered_feasible_node_raises(featurizer):
    c = EdgeCluster(nodes=PAPER_NODES)
    c.profile(250.0)
    provider = StaticProvider({"node-high": 600.0})   # others uncovered
    with pytest.raises(KeyError):
        featurizer(c.feature_cache(), [Task()], provider)


@pytest.mark.parametrize("seed", range(8))
def test_usable_matches_feasible_any(seed):
    """``usable`` is ``feasible(...).any(axis=0)`` without the (B, N)
    array, also where needs sit at a node's free cpu or memory or one
    float64 ulp to either side of it."""
    rng = np.random.default_rng(seed)
    c = random_cluster(rng, 40)
    cache = c.feature_cache()
    n = int(rng.integers(1, 4)) if seed % 2 else int(rng.integers(4, 16))
    j = rng.integers(0, 40, n)
    step = rng.choice([-np.inf, 0.0, np.inf], n)  # 0.0: exactly at it
    cpu = np.where(step == 0.0, cache.free_cpu[j],
                   np.nextafter(cache.free_cpu[j], step))
    mem = np.where(step == 0.0, cache.free_mem[j],
                   np.nextafter(cache.free_mem[j], step))
    low = rng.random(n) < 0.5           # the other need fits node j
    cpu[low] *= rng.uniform(0.0, 1.0, n)[low]
    mem[~low] *= rng.uniform(0.0, 1.0, n)[~low]
    for sl in [slice(t, t + 1) for t in range(n)] + [slice(None)]:
        np.testing.assert_array_equal(
            cache.usable(cpu[sl], mem[sl], cache.node_ok()),
            cache.feasible(cpu[sl], mem[sl]).any(axis=0))


def test_topology_changes_rebuild():
    c = EdgeCluster(nodes=PAPER_NODES)
    c.profile(250.0)
    cache = c.feature_cache()
    assert cache.n == 3
    c.add_node(NodeSpec("n-new", 1.0, 2048, 100.0))
    c.nodes["n-new"].avg_time_ms = 100.0
    assert c.feature_cache().n == 4
    assert_cache_parity(c, [Task()])
    c.remove_node("node-high")
    assert c.feature_cache().n == 3
    assert_cache_parity(c, [Task()])


def test_invalidate_features_escape_hatch():
    c = EdgeCluster(nodes=PAPER_NODES)
    c.profile(250.0)
    c.feature_cache()
    # unsupported surgery: swap a node's state object wholesale
    from repro.core.cluster import NodeState
    c.nodes["node-high"] = NodeState(spec=c.nodes["node-high"].spec,
                                     load=0.5, avg_time_ms=123.0)
    c.invalidate_features()
    assert_cache_parity(c, [Task()])
    # the rebuild must ADOPT the surgically-inserted state: later direct
    # mutations have to be dirty-tracked like any other node's
    c.nodes["node-high"].load = 0.9
    assert_cache_parity(c, [Task()])


def test_removed_node_late_write_stays_o_changed():
    """A write to a NodeState after remove_node must neither corrupt the
    cache nor demote sync to a full rebuild."""
    c = EdgeCluster(nodes=PAPER_NODES)
    c.profile(250.0)
    c.feature_cache()
    ghost = c.nodes["node-high"]
    c.remove_node("node-high")
    cache = c.feature_cache()                 # rebuild for the new topology
    ghost.completed += 1                      # late completion write
    assert not c._dirty                       # detached: nothing marked
    assert c.feature_cache() is cache
    assert_cache_parity(c, [Task()])


def test_trace_provider_batch_respects_custom_at():
    """A user trace with a 24-entry .values but its OWN .at semantics must
    be sampled through .at — batch must equal scalar bit-for-bit."""
    class StepTrace:
        def __init__(self, values):
            self.values = values              # 24-long, but NOT interpolated

        def at(self, hour):
            return self.values[int(hour) % 24]

    c = EdgeCluster(nodes=PAPER_NODES)
    c.profile(250.0)
    tr = StepTrace(tuple(float(100 + 10 * i) for i in range(24)))
    provider = TraceProvider({"node-high": tr},
                             fallback=StaticProvider.from_cluster(c))
    from repro.core.providers import intensity_batch
    hours = np.array([0.25, 7.9, 13.5])
    grid = intensity_batch(provider, ["node-high", "node-green"], hours)
    for s, hr in enumerate(hours):
        assert grid[s, 0] == provider.intensity("node-high", float(hr))
        assert grid[s, 1] == provider.intensity("node-green", float(hr))


def test_static_provider_queried_once_across_steps():
    """TIME_INVARIANT providers are memoized: N queries total, not N per
    step."""
    calls = []

    class CountingStatic(StaticProvider):
        def intensity(self, node, hour=0.0):
            calls.append(node)
            return super().intensity(node, hour)

    c = EdgeCluster(nodes=PAPER_NODES)
    c.profile(250.0)
    provider = CountingStatic({n.name: n.carbon_intensity
                               for n in PAPER_NODES})
    for hour in (0.0, 1.0, 2.0):
        featurize_cached(c.feature_cache(), [Task()], provider, hour)
    assert len(calls) == 3                # one per node, ever


def test_time_varying_provider_requeried_per_hour():
    traces = {n.name: synthetic_trace(n.region, n.carbon_intensity)
              for n in PAPER_NODES}
    provider = TraceProvider(traces)
    c = EdgeCluster(nodes=PAPER_NODES)
    c.profile(250.0)
    for hour in (0.0, 6.0, 13.0):
        assert_cache_parity(c, [Task()], provider, hour)


def test_fallback_provider_parity():
    rng = np.random.default_rng(7)
    c = random_cluster(rng, 8)
    names = list(c.nodes)
    traces = {names[0]: synthetic_trace("r", 400.0)}
    provider = FallbackProvider(TraceProvider(traces),
                                StaticProvider.from_cluster(c))
    assert_cache_parity(c, [random_task(rng) for _ in range(3)],
                        provider, now_hour=9.5)


def test_select_batch_cached_vs_fresh_vs_oracle():
    rng = np.random.default_rng(11)
    c = random_cluster(rng, 64)
    tasks = [random_task(rng) for _ in range(16)]
    w = MODES["green"]
    cached = VectorizedPolicy(backend="numpy")
    F, names = featurize(c, tasks)
    oracle = WeightedScoringPolicy()
    assert (cached.select_batch(c, tasks, w)
            == cached._select_from_features(F, names, w)
            == oracle.select_batch(c, tasks, w))


def test_dedup_matches_per_task_selection():
    """Duplicate resource profiles share one scored row — selections must
    equal the undeduped per-task path."""
    rng = np.random.default_rng(13)
    c = random_cluster(rng, 16)
    base = [random_task(rng) for _ in range(3)]
    tasks = [base[i % 3] for i in range(12)]        # heavy duplication
    w = MODES["balanced"]
    cached = VectorizedPolicy(backend="numpy")
    batch = cached.select_batch(c, tasks, w)
    singles = [cached.select(c, t, w) for t in tasks]
    assert batch == singles


def test_chunked_scoring_matches_unchunked():
    rng = np.random.default_rng(17)
    c = random_cluster(rng, 32)
    tasks = [random_task(rng) for _ in range(24)]   # all-distinct profiles
    w = MODES["green"]
    small = VectorizedPolicy(backend="numpy")
    small._CHUNK_ELEMS = 64                          # force many chunks
    big = VectorizedPolicy(backend="numpy")
    assert (small.select_batch(c, tasks, w)
            == big.select_batch(c, tasks, w))


# ---------------------------------------------------------------------------
# hypothesis-backed randomized sequences (optional extra)
# ---------------------------------------------------------------------------

try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:                                   # pragma: no cover
    HAVE_HYPOTHESIS = False


if HAVE_HYPOTHESIS:

    @settings(max_examples=30, deadline=None)
    @given(
        seed=st.integers(0, 2**16),
        ops=st.lists(st.tuples(st.integers(0, 2),
                               st.floats(0.0, 1.0),
                               st.floats(0.0, 1.0)),
                     min_size=1, max_size=10),
    )
    def test_hypothesis_mutation_sequences(seed, ops):
        rng = np.random.default_rng(seed)
        c = random_cluster(rng, int(rng.integers(2, 10)))
        provider = StaticProvider.from_cluster(c)
        names = list(c.nodes)
        for kind, a, b in ops:
            name = names[int(a * (len(names) - 1))]
            stt = c.nodes[name]
            if kind == 0:
                stt.load = b
            elif kind == 1:
                stt.mem_used_mb = b * stt.spec.mem_mb
            else:
                stt.avg_time_ms = 50.0 + 900.0 * b
            assert_cache_parity(c, [Task(cpu=0.05, mem_mb=8.0)], provider)
