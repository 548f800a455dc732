"""Mixture-of-experts block: top-k routing with sort-based capacity dispatch.

Two dispatch paths:

1. **shard_map expert-parallel** (production, beyond-paper §Perf change):
   tokens are split across the ``model`` axis, locally sorted into
   per-expert capacity buckets, exchanged with an explicit
   ``jax.lax.all_to_all``, run through the locally-resident expert weights,
   and exchanged back. This replaces GSPMD's handling of the cross-sharded
   scatter/gather — which materialises and all-reduces the *entire*
   (E, C, D) grouped buffer per layer per pass (~200 GB/device/layer
   observed for qwen2-moe train_4k) — with the minimal a2a volume
   (~tokens*k*cf*D bytes). Used when a mesh with a ``model`` axis is
   active, the padded expert count divides it, and the local token count
   divides it.

2. **dense GSPMD path**: the original sort + scatter into a global
   (E, C, D) buffer, under a mesh when the shard_map path is not viable.

Both mesh paths keep capacity buckets (factor 1.25), so they can drop a
token's expert when routing is uneven. Without a mesh (one chip: serving,
the CPU tests) the layer takes the **held-expert path**, which drops
nothing: rows are sorted by expert into groups padded to a row tile and
run through one grouped matmul (``moe_gmm`` on the chip, ``ragged_dot``
elsewhere). It computes only the experts this chip holds
(``moe.experts_held`` from ``moe.expert_offset``; all of them by default)
while the router scores all of them, so on one chip of an expert-parallel
deployment the layer gives that chip's part of the result.

Expert weights may be padded to ``moe.e_pad`` (qwen2-moe: 60 -> 64) so the
expert axis divides the model axis; padded experts are router-masked to
-inf and unreachable. Switch-style load-balance aux.

Every path also returns how many rows it routed to each held expert, the
``(n_held,)`` int32 count that serving accumulates per layer in its cache.
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.common import ParamSpec, activation
from repro.models import mlp as mlp_mod
from repro.sharding import constraints
from repro.sharding.constraints import constrain

CAPACITY_FACTOR = 1.25
# The routed experts' weights. On one chip a layer scan may hand the held
# path these stacked over layers with the layer's index as ``p["layer"]``.
EXPERT_WEIGHTS = ("w_gate", "w_up", "w_down")


def moe_spec(cfg: ModelConfig) -> Dict:
    m = cfg.moe
    D, E, F = cfg.d_model, m.e_pad, m.expert_ff
    Eh = m.n_held
    spec = {
        # Larger router init: near-uniform routing makes top-k selection
        # tie-sensitive to e-8 numerics across differently-compiled graphs
        # (prefill vs decode), which shows up as spurious test mismatches.
        "router": ParamSpec((D, E), ("embed", "expert"), scale=0.5),
        "w_gate": ParamSpec((Eh, D, F), ("expert", "embed", "ff")),
        "w_up": ParamSpec((Eh, D, F), ("expert", "embed", "ff")),
        "w_down": ParamSpec((Eh, F, D), ("expert", "ff", "embed")),
    }
    if m.num_shared_experts:
        spec["shared"] = mlp_mod.mlp_spec(cfg, m.num_shared_experts * m.expert_ff, True)
        if m.shared_gate:
            spec["shared_gate"] = ParamSpec((D, 1), ("embed", None))
    if m.dense_residual_ff:
        spec["dense"] = mlp_mod.mlp_spec(cfg, m.dense_residual_ff, True)
    return spec


def _capacity(tokens: int, top_k: int, num_experts: int) -> int:
    c = int(tokens * top_k * CAPACITY_FACTOR / num_experts)
    return max(8, -(-c // 8) * 8)  # round up to multiple of 8


def route(cfg: ModelConfig, router_w, x_flat) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """x_flat: (T, D) -> (weights (T,k), idx (T,k), aux_loss scalar)."""
    m = cfg.moe
    logits = jnp.einsum("td,de->te", x_flat, router_w).astype(jnp.float32)
    if m.e_pad > m.num_experts:
        pad_mask = jnp.arange(m.e_pad) >= m.num_experts
        logits = jnp.where(pad_mask[None], -1e30, logits)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, idx = jax.lax.top_k(probs, m.top_k)
    if m.norm_topk_prob:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    if m.routed_scaling_factor != 1.0:
        weights = weights * m.routed_scaling_factor
    # Switch-style load-balance loss: E * sum_e f_e * p_e (real experts).
    T = x_flat.shape[0]
    density = jnp.zeros((m.e_pad,), jnp.float32).at[idx.reshape(-1)].add(1.0)
    density = density / (T * m.top_k)
    p_mean = jnp.mean(probs, axis=0)
    aux = m.num_experts * jnp.sum(density * p_mean) * m.router_aux_weight
    return weights.astype(x_flat.dtype), idx, aux


# ---------------------------------------------------------------------------
# Local dispatch/combine helpers (shared by both paths)
# ---------------------------------------------------------------------------


def _dispatch(x_flat, idx, E_buckets: int, C: int):
    """Sort tokens by expert into an (E_buckets*C+1, D) buffer.

    Returns (buffer_without_drop_row (E_buckets, C, D), dest_tk (T*k,)).
    """
    T, D = x_flat.shape
    k = idx.shape[1]
    flat_e = idx.reshape(-1)
    order = jnp.argsort(flat_e)
    sorted_e = flat_e[order]
    token_of = order // k
    seg_start = jnp.searchsorted(sorted_e, jnp.arange(E_buckets), side="left")
    rank = jnp.arange(T * k) - seg_start[sorted_e]
    keep = rank < C
    dest = jnp.where(keep, sorted_e * C + rank, E_buckets * C)
    buf = jnp.zeros((E_buckets * C + 1, D), x_flat.dtype)
    buf = buf.at[dest].set(x_flat[token_of], mode="drop")
    dest_tk = jnp.zeros((T * k,), jnp.int32).at[order].set(dest.astype(jnp.int32))
    return buf[:-1].reshape(E_buckets, C, D), dest_tk


def _combine(out_grouped, dest_tk, weights):
    """Inverse of _dispatch: gather expert outputs back per (token, k)."""
    EC, D = out_grouped.shape[0] * out_grouped.shape[1], out_grouped.shape[2]
    T, k = weights.shape
    out_flat = out_grouped.reshape(EC, D)
    out_padded = jnp.concatenate([out_flat, jnp.zeros((1, D), out_flat.dtype)])
    safe = jnp.minimum(dest_tk, EC)  # drop bucket -> zero row
    gathered = out_padded[safe].reshape(T, k, D)
    return jnp.einsum("tkd,tk->td", gathered, weights.astype(out_flat.dtype))


def _expert_mlp(cfg, grouped, w_gate, w_up, w_down):
    act = activation(cfg.act)
    h = jnp.einsum("ecd,edf->ecf", grouped, w_up)
    h = h * act(jnp.einsum("ecd,edf->ecf", grouped, w_gate))
    return jnp.einsum("ecf,efd->ecd", h, w_down)


# ---------------------------------------------------------------------------
# Path 1: shard_map expert parallelism
# ---------------------------------------------------------------------------


def _shardmap_viable(cfg: ModelConfig, T: int):
    mesh = constraints._current_mesh()
    if mesh is None or "model" not in mesh.axis_names:
        return None
    msize = int(mesh.shape["model"])
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dp_size = math.prod(int(mesh.shape[a]) for a in dp) if dp else 1
    m = cfg.moe
    if m.e_pad % msize:
        return None
    if T % dp_size or (T // dp_size) % msize:
        return None
    return mesh, dp, dp_size, msize


def _moe_forward_shardmap(cfg: ModelConfig, p, x, mesh, dp, dp_size, msize):
    m = cfg.moe
    B, S, D = x.shape
    T = B * S
    E_pad, k = m.e_pad, m.top_k
    T_loc = T // dp_size              # tokens per data row
    T_m = T_loc // msize              # tokens per (data, model) shard
    C_m = _capacity(T_m, k, m.num_experts)
    dp_spec = dp if len(dp) > 1 else (dp[0] if dp else None)

    def local_fn(x_loc, router_w, w_gate, w_up, w_down):
        # x_loc: (T_loc, D) — identical across the model axis; take our slice.
        mi = jax.lax.axis_index("model")
        xm = jax.lax.dynamic_slice_in_dim(x_loc, mi * T_m, T_m, axis=0)
        weights, idx, aux = route(cfg, router_w, xm)
        buf, dest_tk = _dispatch(xm, idx, E_pad, C_m)        # (E_pad, C_m, D)
        # a2a: send each expert bucket to its owning model shard.
        recv = jax.lax.all_to_all(buf, "model", split_axis=0, concat_axis=1,
                                  tiled=True)                # (E_loc, msize*C_m, D)
        out = _expert_mlp(cfg, recv, w_gate, w_up, w_down)
        back = jax.lax.all_to_all(out, "model", split_axis=1, concat_axis=0,
                                  tiled=True)                # (E_pad, C_m, D)
        ym = _combine(back, dest_tk, weights)                # (T_m, D)
        y_loc = jax.lax.all_gather(ym, "model", axis=0, tiled=True)  # (T_loc, D)
        aux = jax.lax.pmean(aux, "model")
        rows = jax.lax.psum(_expert_rows(idx, E_pad), ("model",) + dp)
        if dp:
            aux = jax.lax.pmean(aux, dp)
        return y_loc, aux, rows

    in_specs = (
        P(dp_spec, None),                 # x_flat (T, D)
        P(None, None),                    # router (replicated)
        P("model", None, None),           # w_gate
        P("model", None, None),           # w_up
        P("model", None, None),           # w_down
    )
    out_specs = (P(dp_spec, None), P(), P())
    y, aux, rows = jax.shard_map(
        local_fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )(x.reshape(T, D), p["router"], p["w_gate"], p["w_up"], p["w_down"])
    return y.reshape(B, S, D), aux, rows


# ---------------------------------------------------------------------------
# Path 2: dense GSPMD path (mesh fallback)
# ---------------------------------------------------------------------------


def _moe_forward_dense(cfg: ModelConfig, p, x):
    m = cfg.moe
    B, S, D = x.shape
    T = B * S
    x_flat = constrain(x.reshape(T, D), "batch", None)
    weights, idx, aux = route(cfg, p["router"], x_flat)
    C = _capacity(T, m.top_k, m.num_experts)
    grouped, dest_tk = _dispatch(x_flat, idx, m.e_pad, C)
    grouped = constrain(grouped, "expert", None, None)
    out = _expert_mlp(cfg, grouped, p["w_gate"], p["w_up"], p["w_down"])
    out = constrain(out, "expert", None, None)
    y = _combine(out, dest_tk, weights)
    return y.reshape(B, S, D), aux, _expert_rows(idx, m.e_pad)


def _expert_rows(idx, n: int):
    """Rows routed to each of experts [0, n): (n,) int32."""
    return jnp.zeros((n,), jnp.int32).at[idx.reshape(-1)].add(1)


# ---------------------------------------------------------------------------
# Path 3: held experts, dropless (one chip)
# ---------------------------------------------------------------------------

MIN_TILE, MAX_TILE = 16, 512


def row_tile(rows: int, num_experts: int) -> int:
    """Row tile of the grouped matmul: the power of two at or above the
    rows an expert gets on average, within [16, 512] (16 is the bf16
    sublane tile; 512 rows keep one expert's weights resident across few
    tiles in prefill)."""
    mean = -(-rows // num_experts)
    return int(min(MAX_TILE, max(MIN_TILE, 1 << (mean - 1).bit_length())))


def held_layout(m, idx, tm: int) -> Dict[str, jnp.ndarray]:
    """Tile layout of the (token, k) rows whose expert is held here.

    Rows are sorted by held expert; each expert's group starts on a tile
    boundary. ``dest`` (T*k,) is each row's slot (``slots``, one past the
    buffer, for a row of an expert not held); ``sizes`` (n_held,) the
    padded group sizes; ``tile_expert`` the expert of each tile (past the
    last group, that of the last group); ``n_valid`` (1,) the tiles that
    hold rows; ``rows`` (n_held,) the unpadded counts."""
    E = m.n_held
    M = idx.size
    slots = -(-(M + E * (tm - 1)) // tm) * tm
    local = idx.reshape(-1) - m.expert_offset
    key = jnp.where((local >= 0) & (local < E), local, E)
    rows = jnp.zeros((E + 1,), jnp.int32).at[key].add(1)
    sizes = (rows[:E] + tm - 1) // tm * tm
    ends = jnp.cumsum(sizes)
    pstart = jnp.concatenate([ends - sizes, jnp.full((1,), slots, jnp.int32)])
    start = jnp.cumsum(rows) - rows
    order = jnp.argsort(key, stable=True)
    skey = key[order]
    dest_sorted = jnp.where(skey < E,
                            pstart[skey] + jnp.arange(M) - start[skey], slots)
    dest = jnp.zeros((M,), jnp.int32).at[order].set(dest_sorted)
    n_valid = ends[-1] // tm
    tiles = jnp.arange(slots // tm) * tm
    last = jnp.max(jnp.where(sizes > 0, jnp.arange(E), 0))
    tile_expert = jnp.where(tiles < ends[-1],
                            jnp.searchsorted(ends, tiles, side="right"), last)
    return {"dest": dest, "sizes": sizes, "tile_expert":
            tile_expert.astype(jnp.int32), "n_valid": n_valid.reshape(1),
            "rows": rows[:E], "slots": slots}


def _moe_forward_held(cfg: ModelConfig, p, x):
    """Held experts' weighted outputs, no token dropped. x: (B, S, D)."""
    from repro.kernels import ops

    m = cfg.moe
    B, S, D = x.shape
    T = B * S
    x_flat = x.reshape(T, D)
    weights, idx, aux = route(cfg, p["router"], x_flat)
    tm = row_tile(T * m.top_k, m.num_experts)
    lay = held_layout(m, idx, tm)
    slots, dest = lay["slots"], lay["dest"]
    src = jnp.full((slots,), T, jnp.int32).at[dest].set(
        jnp.arange(T * m.top_k, dtype=jnp.int32) // m.top_k, mode="drop")
    x_buf = jnp.concatenate([x_flat, jnp.zeros((1, D), x.dtype)])[src]
    layer = p.get("layer")
    if layer is None:      # weights of this layer alone
        ws = [p[k][None] for k in EXPERT_WEIGHTS]
        layer = jnp.zeros((), jnp.int32)
    else:                  # stacked over layers
        ws = [p[k] for k in EXPERT_WEIGHTS]
    if ops.use_pallas() and cfg.act == "silu":
        y_buf = ops.moe_gmm(x_buf, *ws, lay["sizes"], lay["tile_expert"],
                            lay["n_valid"], layer.reshape(1), tm)
    else:
        y_buf = ops.moe_gmm_ref(x_buf, *[w[layer] for w in ws], lay["sizes"],
                                act=activation(cfg.act))
    held = (dest < slots).reshape(T, m.top_k)
    got = y_buf[jnp.minimum(dest, slots - 1)].reshape(T, m.top_k, D)
    w = jnp.where(held, weights, 0).astype(x.dtype)
    got = jnp.where(held[..., None], got, 0)
    y = jnp.einsum("tkd,tk->td", got, w)
    return y.reshape(B, S, D), aux, lay["rows"]


# ---------------------------------------------------------------------------
# Public entry
# ---------------------------------------------------------------------------


def moe_apply(cfg: ModelConfig, p, x):
    """x: (B, S, D) -> (y, aux_loss, rows): ``rows`` (n_held,) int32 counts
    the rows routed to each held expert."""
    m = cfg.moe
    B, S, D = x.shape
    if constraints._current_mesh() is None:
        y, aux, rows = _moe_forward_held(cfg, p, x)
    else:
        if m.n_held != m.e_pad:
            raise ValueError(f"{cfg.name}: a mesh holds every expert; "
                             f"experts_held={m.experts_held}")
        viable = _shardmap_viable(cfg, B * S)
        if viable is not None:
            y, aux, rows = _moe_forward_shardmap(cfg, p, x, *viable)
        else:
            y, aux, rows = _moe_forward_dense(cfg, p, x)

    if m.num_shared_experts:
        s = mlp_mod.mlp_forward(cfg, p["shared"], x, gated=True)
        if m.shared_gate:
            s = jax.nn.sigmoid(jnp.einsum("bsd,dz->bsz", x, p["shared_gate"])) * s
        y = y + s
    if m.dense_residual_ff:
        y = y + mlp_mod.mlp_forward(cfg, p["dense"], x, gated=True)
    return y, aux, rows


def moe_forward(cfg: ModelConfig, p, x) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, S, D) -> (y, aux_loss)."""
    y, aux, _ = moe_apply(cfg, p, x)
    return y, aux
