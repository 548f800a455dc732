"""Scorer parity: the scalar oracle, the numpy vectorized policy, and the
Pallas node-score kernel (interpret mode) must agree on selections through
the shared ``featurize`` layer — on the paper's three-node scenario and on
randomized clusters up to fleet scale (acceptance criteria for the
policy/provider/engine API)."""
import numpy as np
import pytest

from repro.core.cluster import EdgeCluster, NodeSpec, PAPER_NODES
from repro.core.policy import (COL_VALID, VectorizedPolicy,
                               WeightedScoringPolicy, featurize)
from repro.core.scheduler import MODES, Task, scores, sweep_weights

ORACLE = WeightedScoringPolicy()
NUMPY = VectorizedPolicy(backend="numpy")
PALLAS = VectorizedPolicy(backend="pallas")   # interpret mode on CPU


def random_cluster(rng, n):
    nodes = [NodeSpec(f"n{i}", cpu=float(rng.uniform(0.1, 4.0)),
                      mem_mb=int(rng.integers(64, 2048)),
                      carbon_intensity=float(rng.uniform(10.0, 1200.0)))
             for i in range(n)]
    c = EdgeCluster(nodes=nodes, host_power_w=float(rng.uniform(50.0, 300.0)))
    c.profile(float(rng.uniform(50.0, 1000.0)))
    for st in c.nodes.values():
        st.load = float(rng.uniform(0.0, 1.0))
        st.mem_used_mb = float(rng.uniform(0.0, st.spec.mem_mb))
        st.running = int(rng.integers(0, 5))
    return c


def random_task(rng):
    return Task(cpu=float(rng.uniform(0.01, 1.0)),
                mem_mb=float(rng.uniform(4.0, 256.0)),
                base_latency_ms=float(rng.uniform(50.0, 500.0)))


def oracle_score(cluster, task, weights, node):
    return float(weights.as_array()
                 @ scores(cluster.nodes[node], task, cluster.host_power_w))


def test_paper_scenario_all_policies_agree():
    c = EdgeCluster(nodes=PAPER_NODES, host_power_w=142.0)
    c.profile(254.85)
    task = Task(cpu=0.1, mem_mb=64, base_latency_ms=254.85)
    expected = {"performance": "node-high", "balanced": "node-high",
                "green": "node-green"}
    for mode, want in expected.items():
        w = MODES[mode]
        assert ORACLE.select(c, task, w) == want
        assert NUMPY.select(c, task, w) == want
        assert PALLAS.select(c, task, w) == want


@pytest.mark.parametrize("seed", range(10))
@pytest.mark.parametrize("mode", ["green", "balanced", "performance"])
def test_scalar_vs_numpy_randomized(seed, mode):
    rng = np.random.default_rng(seed)
    c = random_cluster(rng, int(rng.integers(2, 12)))
    task = random_task(rng)
    w = MODES[mode]
    a = ORACLE.select(c, task, w)
    b = NUMPY.select(c, task, w)
    if a != b:  # only acceptable on an exact float tie
        assert a is not None and b is not None
        assert abs(oracle_score(c, task, w, a)
                   - oracle_score(c, task, w, b)) < 1e-12, (a, b)


@pytest.mark.parametrize("seed", range(6))
def test_scalar_vs_pallas_randomized(seed):
    """The float32 kernel may flip near-ties; require its pick to be within
    float32 resolution of the oracle's best score."""
    rng = np.random.default_rng(100 + seed)
    c = random_cluster(rng, int(rng.integers(2, 10)))
    task = random_task(rng)
    w = sweep_weights(float(rng.uniform(0.0, 0.9)))
    a = ORACLE.select(c, task, w)
    p = PALLAS.select(c, task, w)
    assert (a is None) == (p is None)
    if a is not None and a != p:
        sa, sp = (oracle_score(c, task, w, n) for n in (a, p))
        assert abs(sa - sp) < 1e-5 * max(1.0, abs(sa)), (a, p, sa, sp)


@pytest.mark.parametrize("n", [256, 512])
def test_fleet_scale_parity(n):
    """Acceptance: >=256-node randomized fleets select identically (scalar
    oracle vs numpy vs Pallas-interpret, modulo float32 ties)."""
    rng = np.random.default_rng(n)
    c = random_cluster(rng, n)
    task = random_task(rng)
    for mode in ("green", "performance"):
        w = MODES[mode]
        a = ORACLE.select(c, task, w)
        b = NUMPY.select(c, task, w)
        p = PALLAS.select(c, task, w)
        assert a == b
        if a != p and a is not None and p is not None:
            sa, sp = (oracle_score(c, task, w, x) for x in (a, p))
            assert abs(sa - sp) < 1e-5 * max(1.0, abs(sa))


def test_featurize_is_single_source_of_layout():
    """featurize columns reproduce the scalar component math exactly: for
    every valid node, vector_scores over featurize's first six columns must
    equal weights @ scores(...)."""
    from repro.core.scheduler import vector_scores

    rng = np.random.default_rng(7)
    c = random_cluster(rng, 8)
    task = random_task(rng)
    w = MODES["balanced"]
    F, names = featurize(c, [task])
    totals = vector_scores(F[0, :, :6], w.as_array())
    for j, name in enumerate(names):
        if F[0, j, COL_VALID] > 0.5:
            assert abs(totals[j] - oracle_score(c, task, w, name)) < 1e-12


def test_featurize_batch_rows_independent():
    """Row i of a batched featurize equals featurizing task i alone."""
    rng = np.random.default_rng(11)
    c = random_cluster(rng, 5)
    tasks = [random_task(rng) for _ in range(4)]
    F, _ = featurize(c, tasks)
    for i, t in enumerate(tasks):
        Fi, _ = featurize(c, [t])
        np.testing.assert_array_equal(F[i], Fi[0])


def test_infeasible_everywhere_returns_none():
    c = random_cluster(np.random.default_rng(13), 4)
    huge = Task(cpu=100.0, mem_mb=1e9)
    w = MODES["green"]
    assert ORACLE.select(c, huge, w) is None
    assert NUMPY.select(c, huge, w) is None
    assert PALLAS.select(c, huge, w) is None


def test_select_batch_matches_select():
    rng = np.random.default_rng(17)
    c = random_cluster(rng, 6)
    tasks = [random_task(rng) for _ in range(8)]
    w = MODES["green"]
    batch = NUMPY.select_batch(c, tasks, w)
    singles = [NUMPY.select(c, t, w) for t in tasks]
    assert batch == singles
    assert batch == ORACLE.select_batch(c, tasks, w)


def test_pallas_compile_count_bounded_across_fleet_sizes():
    """The Pallas column path pads (B, N) to power-of-two shape buckets,
    so a sweep over many distinct fleet/batch sizes may only add as many
    jit entries as there are distinct buckets — not one per (B, N)."""
    from repro.kernels import node_score as ns

    fn = ns.select_best_columns
    pol = VectorizedPolicy(backend="pallas")
    sweep = [(1, 3), (2, 5), (3, 9), (2, 17), (4, 33), (1, 40),
             (5, 65), (2, 100), (3, 129), (1, 200)]
    buckets = set()
    rng = np.random.default_rng(0)
    baseline = fn._cache_size()
    for b, n in sweep:
        c = random_cluster(rng, n)
        tasks = [random_task(rng) for _ in range(b)]
        pol.select_batch(c, tasks, MODES["green"])
        buckets.add((pol._bucket(len({(t.cpu, t.mem_mb) for t in tasks})),
                     pol._bucket(n)))
    grown = fn._cache_size() - baseline
    assert grown <= len(buckets), (grown, sorted(buckets))
    assert len(buckets) < len(sweep)           # bucketing actually coalesces


def _engine(backend, n=256, seed=31, **kw):
    from repro.core.api import CarbonEdgeEngine

    return CarbonEdgeEngine(random_cluster(np.random.default_rng(seed), n),
                            policy=VectorizedPolicy(backend=backend), **kw)


def _continuous_batches(seed, steps=3, b=48):
    """Distinct continuous profiles, memory up to about half the largest
    node's, so the feasibility filter moves winners."""
    rng = np.random.default_rng(seed)
    return [[Task(cpu=float(rng.uniform(0.01, 1.0)),
                  mem_mb=float(rng.uniform(8.0, 1000.0)),
                  base_latency_ms=float(rng.uniform(50.0, 500.0)))
             for _ in range(b)] for _ in range(steps)]


def test_engine_pallas_places_as_numpy_at_fleet_scale():
    """With ``backend="pallas"`` (the column kernel) the engine places a
    256-node fleet with continuous task profiles exactly as the float64
    numpy backend does, step after step."""
    placed = {}
    for backend in ("numpy", "pallas"):
        eng = _engine(backend)
        placed[backend] = [[r.node for r in eng.submit_many(b).step()]
                           for b in _continuous_batches(37)]
    assert [len(p) for p in placed["pallas"]] == [48] * 3
    assert placed["pallas"] == placed["numpy"]


def test_select_columns_span_counts_one_per_step():
    """``select.columns`` counts the steps the column path scored: one per
    engine step on the Pallas backend, with one kernel launch per step."""
    from repro.obs import Observability, StepProfiler

    prof = StepProfiler()
    eng = _engine("pallas", n=64, obs=Observability(profile=prof))
    for b in _continuous_batches(41):
        eng.submit_many(b).step()
    assert prof.count("select.columns") == 3
    assert prof.count("select.launch") == 3


def test_numpy_never_emits_select_columns():
    """The numpy backend scores in ``featurize`` and ``score`` spans and
    never enters the column path's ``select.*`` spans."""
    from repro.obs import Observability, StepProfiler

    prof = StepProfiler()
    eng = _engine("numpy", n=64, obs=Observability(profile=prof))
    for b in _continuous_batches(41):
        eng.submit_many(b).step()
    assert prof.count("score") == 3
    assert prof.count("select.columns") == 0
    assert prof.count("select.launch") == 0


class _NoCache:
    """A cluster-like without a FeatureCache: only the node states."""

    def __init__(self, cluster):
        self.nodes, self.host_power_w = cluster.nodes, cluster.host_power_w


def test_uncached_cluster_like_places_on_pallas_as_numpy():
    """Without a FeatureCache, the Pallas backend scores with the float64
    reference and places exactly as numpy and the scalar oracle do."""
    from repro.obs import StepProfiler

    rng = np.random.default_rng(43)
    c = random_cluster(rng, 40)
    tasks = [random_task(rng) for _ in range(12)]
    pol = VectorizedPolicy(backend="pallas")
    pol.profiler = StepProfiler()
    for mode in ("green", "balanced", "performance"):
        w = MODES[mode]
        got = pol.select_batch(_NoCache(c), tasks, w)
        assert got == NUMPY.select_batch(_NoCache(c), tasks, w)
        assert got == ORACLE.select_batch(c, tasks, w)
    assert pol.profiler.count("select.launch") == 0


def test_cached_column_path_matches_fresh_at_fleet_scale():
    """At fleet scale the cached numpy path (``featurize_cached`` in
    chunks) selects exactly what the fresh float64 reference
    (``featurize`` + ``_select_from_features``) selects."""
    rng = np.random.default_rng(23)
    n = 5000
    c = random_cluster(rng, n)
    tasks = [random_task(rng) for _ in range(6)]
    cached = VectorizedPolicy(backend="numpy")
    for mode in ("green", "performance"):
        w = MODES[mode]
        F, names = featurize(c, tasks)
        assert (cached.select_batch(c, tasks, w)
                == NUMPY._select_from_features(F, names, w))
