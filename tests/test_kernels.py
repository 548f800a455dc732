"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import node_score as ns
from repro.kernels import ops

KEY = jax.random.PRNGKey(0)


def rand(key, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32) * 0.5
    return x.astype(dtype)


@pytest.mark.parametrize("B,H,K,S,hd", [
    (1, 2, 2, 128, 64),
    (2, 4, 2, 256, 64),
    (2, 4, 1, 256, 128),
    (1, 8, 8, 384, 32),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_shapes(B, H, K, S, hd, dtype):
    q = rand(jax.random.fold_in(KEY, 1), (B, H, S, hd), dtype)
    k = rand(jax.random.fold_in(KEY, 2), (B, K, S, hd), dtype)
    v = rand(jax.random.fold_in(KEY, 3), (B, K, S, hd), dtype)
    out = ops.flash_attention(q, k, v, causal=True)
    ref = ops.flash_attention_ref(q, k, v, causal=True)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("kwargs", [
    dict(causal=True, window=64),
    dict(causal=True, window=128),
    dict(causal=False),
    dict(causal=True, softcap=50.0),
])
def test_flash_attention_variants(kwargs):
    B, H, K, S, hd = 2, 4, 2, 256, 64
    q = rand(jax.random.fold_in(KEY, 4), (B, H, S, hd), jnp.float32)
    k = rand(jax.random.fold_in(KEY, 5), (B, K, S, hd), jnp.float32)
    v = rand(jax.random.fold_in(KEY, 6), (B, K, S, hd), jnp.float32)
    out = ops.flash_attention(q, k, v, **kwargs)
    ref = ops.flash_attention_ref(q, k, v, **kwargs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("B,H,K,S,hd", [
    (1, 2, 2, 256, 64),
    (2, 4, 2, 512, 64),
    (2, 8, 2, 512, 128),
])
@pytest.mark.parametrize("pos", [0, 100, 255])
def test_decode_attention(B, H, K, S, hd, pos):
    q = rand(jax.random.fold_in(KEY, 7), (B, H, hd), jnp.float32)
    k = rand(jax.random.fold_in(KEY, 8), (B, K, S, hd), jnp.float32)
    v = rand(jax.random.fold_in(KEY, 9), (B, K, S, hd), jnp.float32)
    out = ops.decode_attention(q, k, v, jnp.int32(pos))
    ref = ops.decode_attention_ref(q, k, v, jnp.int32(pos))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_decode_attention_window():
    B, H, K, S, hd = 2, 4, 4, 512, 64
    q = rand(jax.random.fold_in(KEY, 10), (B, H, hd), jnp.float32)
    k = rand(jax.random.fold_in(KEY, 11), (B, K, S, hd), jnp.float32)
    v = rand(jax.random.fold_in(KEY, 12), (B, K, S, hd), jnp.float32)
    out = ops.decode_attention(q, k, v, jnp.int32(300), window=64)
    ref = ops.decode_attention_ref(q, k, v, jnp.int32(300), window=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("B,H,L,N,P", [
    (1, 2, 16, 8, 8),
    (2, 4, 32, 16, 8),
    (2, 2, 64, 64, 64),
])
def test_mamba2_chunk(B, H, L, N, P):
    xdt = rand(jax.random.fold_in(KEY, 13), (B, H, L, P), jnp.float32) * 0.3
    Bh = rand(jax.random.fold_in(KEY, 14), (B, H, L, N), jnp.float32) * 0.3
    Ch = rand(jax.random.fold_in(KEY, 15), (B, H, L, N), jnp.float32) * 0.3
    dA = -jnp.abs(rand(jax.random.fold_in(KEY, 16), (B, H, L), jnp.float32)) * 0.1
    cum = jnp.cumsum(dA, axis=-1)
    st = rand(jax.random.fold_in(KEY, 17), (B, H, N, P), jnp.float32) * 0.3
    y, s = ops.mamba2_chunk(xdt, Bh, Ch, cum, st.astype(jnp.float32))
    yr, sr = ops.mamba2_chunk_ref(xdt, Bh, Ch, cum, st.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), atol=1e-4, rtol=1e-4)


def test_mamba2_chunk_matches_model_scan():
    """The kernel's chunk semantics equal models/ssm.py's chunk_body."""
    from repro.configs.registry import reduced_config
    from repro.models import ssm, transformer

    cfg = reduced_config("zamba2-2.7b")
    p = transformer.init_params(cfg, jax.random.PRNGKey(0))
    # locate a mamba block param tree
    blk = jax.tree.map(lambda a: a[0], p["pattern"]["0"])["mamba"]
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 64, cfg.d_model)) * 0.1
    out_ref = ssm.mamba2_forward(cfg, blk, x)
    assert not bool(jnp.any(jnp.isnan(out_ref)))


def _node_scores(f, w):
    return ns.node_scores(jnp.asarray(f), jnp.asarray(w),
                          interpret=not ops.use_pallas())


@pytest.mark.parametrize("n", [1024, 4096])
def test_node_scores(n):
    rng = np.random.default_rng(0)
    f = np.abs(rng.standard_normal((n, 8))).astype(np.float32)
    f[:, 6] = (f[:, 6] > 0.4).astype(np.float32)
    w = np.array([0.2, 0.2, 0.15, 0.15, 0.3, 0, 0, 0], np.float32)
    out = _node_scores(f, w)
    ref = ops.node_scores_ref(jnp.asarray(f), jnp.asarray(w))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6, rtol=1e-6)


def test_node_scores_matches_scheduler():
    """Kernel oracle must equal core/scheduler.vector_scores on valid rows."""
    from repro.core.scheduler import vector_scores

    rng = np.random.default_rng(1)
    f6 = np.abs(rng.standard_normal((256, 6))).astype(np.float32)
    w5 = np.array([0.15, 0.15, 0.10, 0.10, 0.50])
    ref = vector_scores(f6, w5)
    f8 = np.concatenate([f6, np.ones((256, 1), np.float32),
                         np.zeros((256, 1), np.float32)], axis=1)
    w8 = np.concatenate([w5, np.zeros(3)]).astype(np.float32)
    out = _node_scores(f8, w8)
    np.testing.assert_allclose(np.asarray(out), ref.astype(np.float32),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("B,n", [(4, 1024), (3, 100), (1, 7)])
def test_node_scores_batched(B, n):
    """One-launch batched scorer == vmap'd reference == per-row single."""
    rng = np.random.default_rng(3)
    f = np.abs(rng.standard_normal((B, n, 8))).astype(np.float32)
    f[:, :, 6] = (f[:, :, 6] > 0.4).astype(np.float32)
    w = np.array([0.2, 0.2, 0.15, 0.15, 0.3, 0, 0, 0], np.float32)
    out = ops.node_scores_batched(jnp.asarray(f), jnp.asarray(w))
    ref = ops.node_scores_batched_ref(jnp.asarray(f), jnp.asarray(w))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-6, rtol=1e-6)
    for b in range(B):
        row = _node_scores(f[b], w)
        np.testing.assert_allclose(np.asarray(out[b]), np.asarray(row),
                                   atol=1e-6, rtol=1e-6)


W8 = np.array([0.2, 0.2, 0.15, 0.15, 0.3, 0, 0, 0], np.float32)


def _columns(free_cpu, free_mem, rest, need):
    """The column kernel's operands (layouts in ``kernels/node_score.py``)
    from float64 free cpu and memory, the (5, N) float32 rows load, avg
    time, running, I x E_est and node_ok, and (U, 2) float64 needs."""
    nodes = np.concatenate([np.stack([free_cpu, free_mem]), rest]
                           ).astype(np.float32)
    keys = np.concatenate([ns.f64_keys(free_cpu), ns.f64_keys(free_mem)])
    tkeys = np.concatenate([ns.f64_keys(need[:, 0]),
                            ns.f64_keys(need[:, 1])]).T
    return tuple(map(jnp.asarray, (nodes, keys, need.astype(np.float32),
                                   tkeys)))


def _cell_tensor(free_cpu, free_mem, rest, need):
    """The (U, N, 8) float32 feature tensor of the same cells, built as
    ``featurize_cached`` builds it: fractions and feasibility in float64."""
    U, N = need.shape[0], free_cpu.shape[0]
    F = np.zeros((U, N, 8))
    for col, free, k in ((0, free_cpu, 0), (1, free_mem, 1)):
        frac = np.ones((U, N))
        np.divide(free[None, :], need[:, k:k + 1], out=frac,
                  where=need[:, k:k + 1] > 0)
        F[:, :, col] = frac
    F[:, :, 2:6] = rest[:4].T[None]
    F[:, :, 6] = ((rest[4] > 0.5)[None, :]
                  & (free_cpu[None, :] >= need[:, :1])
                  & (free_mem[None, :] >= need[:, 1:]))
    return F.astype(np.float32)


def _random_columns(U, N, seed):
    rng = np.random.default_rng(seed)
    free_cpu = rng.uniform(0.0, 4.0, N)
    free_mem = rng.uniform(0.0, 4096.0, N)
    rest = np.stack([rng.uniform(0.0, 1.0, N), rng.uniform(0.0, 2.0, N),
                     rng.integers(0, 5, N).astype(float),
                     rng.uniform(0.0, 3.0, N),
                     (rng.uniform(0.0, 1.0, N) > 0.3).astype(float)])
    need = np.stack([rng.uniform(0.0, 2.0, U), rng.uniform(0.0, 2048.0, U)],
                    axis=1)
    return free_cpu, free_mem, rest, need


@pytest.mark.parametrize("B,n", [(5, 300), (1, 7), (3, 1024), (2, 1500)])
def test_select_best_fused_matches_scores_argmax(B, n):
    """The column select kernel (trace name ``select_best_fused``) ==
    argmax over the score kernel on the same cells' feature tensor, with
    the winner's score returned (the host never sees the (B, N) matrix)."""
    case = _random_columns(B, n, seed=5 * n + B)
    idx, val = ops.select_best_node_columns(*_columns(*case),
                                            jnp.asarray(W8))
    scores = np.asarray(ops.node_scores_batched(
        jnp.asarray(_cell_tensor(*case)), jnp.asarray(W8)))
    ref = np.argmax(scores, axis=1)
    np.testing.assert_array_equal(np.asarray(idx), ref)
    np.testing.assert_array_equal(np.asarray(val), scores[np.arange(B), ref])
    assert np.all(np.asarray(val) > -1e29)      # every row has a winner


def _tie_case(N, pairs):
    """N identical feasible nodes and one row per (a, b) pair, in which
    nodes a and b are the best with exactly equal scores (a lower load)."""
    free = np.full(N, 2.0)
    rest = np.tile(np.array([[0.5], [0.1], [1.0], [0.2], [1.0]]), (1, N))
    rows = []
    for a, b in pairs:
        r = rest.copy()
        r[0, [a, b]] = 0.0
        rows.append(r)
    return free, free * 1000.0, rows, np.array([[1.0, 64.0]])


def test_select_best_fused_tie_prefers_lowest_index():
    """Exact ties must resolve like np.argmax: the lowest node index wins,
    within a tile and across tiles."""
    pairs = [(700, 1900), (3, 4), (1024, 1025)]        # cross/in-tile ties
    free_cpu, free_mem, rows, need = _tie_case(2048, pairs)
    for (a, b), rest in zip(pairs, rows):
        idx, _ = ops.select_best_node_columns(
            *_columns(free_cpu, free_mem, rest, need), jnp.asarray(W8))
        assert int(idx[0]) == a, (a, b, int(idx[0]))


def test_select_best_columns_tie_across_node_tiles():
    """With node tiles of 128, a tie between tile 1 and tile 3 resolves to
    the lowest global index in every task row, over two row tiles."""
    a, b = 130, 390
    free_cpu, free_mem, (rest,), _ = _tie_case(512, [(a, b)])
    need = np.tile([[1.0, 64.0]], (16, 1))
    idx, val = ns.select_best_columns(
        *_columns(free_cpu, free_mem, rest, need), jnp.asarray(W8), bu=8,
        bn=128, interpret=not ops.use_pallas())
    np.testing.assert_array_equal(np.asarray(idx), np.full(16, a))
    assert np.all(np.asarray(val) > 0)


@pytest.mark.parametrize("shift", ["equal", "one_ulp_under"])
def test_select_best_columns_free_exactly_at_need(shift):
    """Node 0, the better one, is feasible when its free cpu equals the
    need exactly and not one float64 ulp under it, although the two free
    values round to the same float32; node 1 (worse, roomy) wins then."""
    free_cpu, free_mem = np.array([0.7, 3.0]), np.array([700.3, 4000.0])
    rest = np.array([[0.0, 0.9], [0.1, 0.1], [0.0, 0.0], [0.0, 0.0],
                     [1.0, 1.0]])
    need = np.array([[0.7, 700.3]])
    if shift == "one_ulp_under":
        free_cpu[0] = np.nextafter(free_cpu[0], -np.inf)
        assert np.float32(free_cpu[0]) == np.float32(need[0, 0])
    idx, val = ops.select_best_node_columns(
        *_columns(free_cpu, free_mem, rest, need), jnp.asarray(W8))
    assert int(idx[0]) == (0 if shift == "equal" else 1)
    assert float(val[0]) > 0


def test_select_best_fused_all_invalid():
    case = _random_columns(2, 64, seed=6)
    case[2][4] = 0.0                                    # no node is ok
    idx, val = ops.select_best_node_columns(*_columns(*case),
                                            jnp.asarray(W8))
    assert np.all(np.asarray(val) < -1e29)     # NEG_INF sentinel: no winner


def _column_case(U, N, seed):
    """A fleet of N nodes (node 0 the best for a small task, copied at
    node 1 and at node N - 1 for exact score ties within and across node
    tiles) and U task profiles, led by the cases the column kernel must
    decide as the host's float64 compare does: cpu and memory one ulp
    above, at and one ulp below node 0's free cpu and free memory, a zero
    need of each, and a task that fits nowhere; then random profiles."""
    from repro.core.cluster import EdgeCluster, NodeSpec
    from repro.core.scheduler import Task

    rng = np.random.default_rng(seed)
    cpu = rng.uniform(0.1, 4.0, N)
    mem = rng.integers(128, 4096, N)
    inten = rng.uniform(10.0, 1200.0, N)
    load = rng.uniform(0.0, 0.95, N)
    used = rng.uniform(0.0, 1.0, N) * mem
    running = rng.integers(0, 5, N)
    cpu[0], mem[0], inten[0], load[0], used[0], running[0] = (
        4.0, 4096, 5.0, 0.0123456789, 7.654321, 0)
    for k in {1, N - 1}:
        cpu[k], mem[k], inten[k], load[k], used[k], running[k] = (
            cpu[0], mem[0], inten[0], load[0], used[0], running[0])
    c = EdgeCluster(nodes=[NodeSpec(f"n{i}", cpu=float(cpu[i]),
                                    mem_mb=int(mem[i]),
                                    carbon_intensity=float(inten[i]))
                           for i in range(N)], host_power_w=142.0)
    c.profile(250.0)
    for i, st in enumerate(c.nodes.values()):
        st.load, st.mem_used_mb = float(load[i]), float(used[i])
        st.running = int(running[i])
    cache = c.feature_cache()
    fc, fm = cache.free_cpu[0], cache.free_mem[0]
    up, down = (lambda x: np.nextafter(x, np.inf),
                lambda x: np.nextafter(x, -np.inf))
    prof = [(up(fc), 1.0), (0.01, up(fm)), (fc, 1.0), (0.01, fm),
            (down(fc), 1.0), (0.01, down(fm)), (0.0, 64.0), (0.5, 0.0),
            (0.01, 1e9)]
    prof += [(rng.uniform(0.01, 2.0), rng.uniform(8.0, 4000.0))
             for _ in range(max(0, U - len(prof)))]
    return cache, [Task(cpu=float(a), mem_mb=float(b)) for a, b in prof[:U]]


@pytest.mark.parametrize("U,N", [(1, 3), (9, 3), (12, 300), (1, 1500),
                                 (40, 1500), (9, 1024)])
def test_select_best_columns_matches_fused_tensor(U, N):
    """The column kernel picks the winners of the float64 reference
    (``featurize_cached`` + argmax), and its scores are, bit for bit, the
    score kernel's on the float32 cast of that tensor: exact ties to the
    lowest index, NEG_INF rows, zero needs, and feasibility one float64
    ulp either side of a node's free cpu and memory, which a float32
    compare decides wrongly."""
    from repro.core.policy import (VectorizedPolicy, featurize_cached,
                                   featurize_columns)

    cache, tasks = _column_case(U, N, seed=U * N)
    w = np.array([0.15, 0.15, 0.1, 0.1, 0.5, 0, 0, 0], np.float32)
    F, _ = featurize_cached(cache, tasks)
    totals = VectorizedPolicy._score_numpy(F, w[:5].astype(np.float64))
    want_i = np.argmax(totals, axis=1)
    some = np.isfinite(totals[np.arange(U), want_i])
    got_i, got_v = ops.select_best_node_columns(
        *map(jnp.asarray, featurize_columns(cache, tasks)), jnp.asarray(w))
    got_i, got_v = np.asarray(got_i), np.asarray(got_v)
    np.testing.assert_array_equal(got_i[some], want_i[some])
    np.testing.assert_allclose(got_v[some], totals[some, want_i[some]],
                               rtol=1e-6)
    assert np.all(got_v[~some] < -1e29)
    s32 = np.asarray(ops.node_scores_batched(jnp.asarray(F, jnp.float32),
                                             jnp.asarray(w)))
    np.testing.assert_array_equal(got_v, s32[np.arange(U), got_i])
    # the case is hard: the leading rows' exact feasibility on node 0
    # differs from a float32 compare's, and node 0 (the best) wins
    # exactly where it is feasible (index 0 with NEG_INF is no winner)
    need = np.array([[t.cpu, t.mem_mb] for t in tasks])
    free = np.array([cache.free_cpu[0], cache.free_mem[0]])
    exact = np.all(free >= need, axis=1)
    f32 = np.all(free.astype(np.float32) >= need.astype(np.float32), axis=1)
    assert not np.array_equal(exact, f32)
    lead = slice(0, min(U, 6))
    won = (np.asarray(got_i) == 0) & (np.asarray(got_v) > 0)
    np.testing.assert_array_equal(won[lead], exact[lead])
    if U >= 9:
        assert np.asarray(got_v)[8] < -1e29        # fits nowhere
