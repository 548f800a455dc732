"""GQA attention: full / sliding-window / cross, train + prefill + decode.

The full-sequence path is *query-block chunked* (flash-style running
log-sum-exp over KV blocks) so prefill_32k never materialises an (S, S)
score matrix. The same math is implemented as a Pallas TPU kernel in
``repro.kernels.flash_attention``; this jnp version is the oracle and the
CPU/dry-run path.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import common, modes
from repro.sharding.constraints import constrain
from repro.models.common import ParamSpec

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------


def attention_spec(cfg: ModelConfig, cross: bool = False) -> Dict:
    D, H, K, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    spec = {
        "wq": ParamSpec((D, H, hd), ("embed", "heads", "head_dim")),
        "wk": ParamSpec((D, K, hd), ("embed", "kv_heads", "head_dim")),
        "wv": ParamSpec((D, K, hd), ("embed", "kv_heads", "head_dim")),
        "wo": ParamSpec((H, hd, D), ("heads", "head_dim", "embed")),
    }
    if cfg.qkv_bias:
        spec["bq"] = ParamSpec((H, hd), ("heads", "head_dim"), "zeros")
        spec["bk"] = ParamSpec((K, hd), ("kv_heads", "head_dim"), "zeros")
        spec["bv"] = ParamSpec((K, hd), ("kv_heads", "head_dim"), "zeros")
    if cfg.qk_norm:
        spec["q_norm"] = ParamSpec((hd,), ("norm",), "zeros")
        spec["k_norm"] = ParamSpec((hd,), ("norm",), "zeros")
    return spec


def _project_q(cfg: ModelConfig, p, x):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    q = constrain(q, "batch", None, "heads", "head_dim")
    if cfg.qkv_bias:
        q = q + p["bq"]
    if cfg.qk_norm:
        q = common.rms_norm(q, p["q_norm"], cfg.norm_eps)
    return q


def _project_kv(cfg: ModelConfig, p, x):
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    k = constrain(k, "batch", None, "kv_heads", "head_dim")
    v = constrain(v, "batch", None, "kv_heads", "head_dim")
    if cfg.qkv_bias:
        k, v = k + p["bk"], v + p["bv"]
    if cfg.qk_norm:
        k = common.rms_norm(k, p["k_norm"], cfg.norm_eps)
    return k, v


def _positions(cfg: ModelConfig, q, k, q_pos, k_pos, mrope_pos):
    if cfg.mrope_sections and mrope_pos is not None:
        q = common.apply_mrope(q, mrope_pos, cfg.rope_theta, cfg.mrope_sections)
        k = common.apply_mrope(k, mrope_pos, cfg.rope_theta, cfg.mrope_sections)
    elif cfg.pos_emb == "rope":
        q = common.apply_rope(q, q_pos, cfg.rope_theta)
        k = common.apply_rope(k, k_pos, cfg.rope_theta)
    return q, k


def _softcap(logits, cap: float):
    if cap and cap > 0:
        return jnp.tanh(logits / cap) * cap
    return logits


# ---------------------------------------------------------------------------
# Chunked full-sequence attention (train / prefill)
# ---------------------------------------------------------------------------


def _attend_dense(cfg, q, k, v, mask):
    """q: (B,Sq,H,hd) k/v: (B,Sk,K,hd) mask: (Sq,Sk) bool (True=keep).

    TPU layout: KV is expanded to the query-head count so the score einsum
    contracts only the (replicated) head_dim — sharding stays on
    (batch, heads) with zero per-score collectives. When heads don't divide
    the model axis, the KV *sequence* is sharded over `model` instead
    (softmax then needs only small (B,H,Sq) all-reduces for max/sum).
    """
    from repro.sharding.constraints import mesh_axis_size

    B, Sq, H, hd = q.shape
    K = k.shape[2]
    if K != H:
        rep = H // K
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    msize = mesh_axis_size("model")
    heads_ok = msize > 0 and H % msize == 0
    if heads_ok:
        k = constrain(k, "batch", None, "heads", None)
        v = constrain(v, "batch", None, "heads", None)
    else:
        k = constrain(k, "batch", "kv_seq", None, None)
        v = constrain(v, "batch", "kv_seq", None, None)
    logits = jnp.einsum("bshk,bthk->bhst", q, k).astype(jnp.float32)
    logits = constrain(logits, "batch", "heads", None, None) if heads_ok \
        else constrain(logits, "batch", None, None, "kv_seq")
    logits = _softcap(logits * (hd ** -0.5), cfg.attn_logit_softcap)
    logits = jnp.where(mask[None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhst,bthk->bshk", probs, v)
    out = constrain(out, "batch", None, "heads", None)
    return out


def _pallas_attention_viable(q, k) -> bool:
    """Route through the Pallas flash kernel: enabled, single-device (the
    kernel is per-shard; inside pjit the jnp path lowers with GSPMD), and
    MXU-aligned shapes."""
    from repro.kernels import ops
    from repro.sharding.constraints import _current_mesh

    if not ops.use_pallas() or _current_mesh() is not None:
        return False
    B, S, H, hd = q.shape
    K = k.shape[2]
    return S % 128 == 0 and k.shape[1] % 128 == 0 and H % K == 0


def chunked_attention(cfg: ModelConfig, q, k, v, *, causal: bool,
                      window: Optional[int], q_block: int = 1024):
    """Flash-style: scan over query blocks; per block, dense vs full K.

    Memory per block is O(q_block * S); the (S,S) matrix never exists.
    Routes through the Pallas flash-attention kernel when viable.
    """
    B, S, H, hd = q.shape
    if _pallas_attention_viable(q, k):
        from repro.kernels import ops

        out = ops.flash_attention(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), causal=causal, window=window,
            softcap=cfg.attn_logit_softcap)
        return out.transpose(0, 2, 1, 3)
    if S <= q_block:
        mask = _make_mask(S, S, 0, causal, window)
        return _attend_dense(cfg, q, k, v, mask)
    nb = S // q_block
    rem = S - nb * q_block

    def body(_, qb_idx):
        start = qb_idx * q_block
        qb = jax.lax.dynamic_slice_in_dim(q, start, q_block, axis=1)
        mask = _make_mask_dyn(q_block, S, start, causal, window)
        return None, _attend_dense(cfg, qb, k, v, mask)

    _, outs = modes.scan(body, None, jnp.arange(nb))
    out = outs.transpose(1, 0, 2, 3, 4).reshape(B, nb * q_block, H, v.shape[-1])
    if rem:
        qb = q[:, nb * q_block:]
        mask = _make_mask_dyn(rem, S, nb * q_block, causal, window)
        out = jnp.concatenate([out, _attend_dense(cfg, qb, k, v, mask)], axis=1)
    return out


def _make_mask(sq, sk, offset, causal, window):
    qi = jnp.arange(sq)[:, None] + offset
    ki = jnp.arange(sk)[None, :]
    m = jnp.ones((sq, sk), bool)
    if causal:
        m &= ki <= qi
    if window is not None:
        m &= ki > qi - window
    return m


def _make_mask_dyn(sq, sk, start, causal, window):
    qi = start + jnp.arange(sq)[:, None]
    ki = jnp.arange(sk)[None, :]
    m = jnp.ones((sq, sk), bool)
    if causal:
        m &= ki <= qi
    if window is not None:
        m &= ki > qi - window
    return m


# ---------------------------------------------------------------------------
# Public block entry points
# ---------------------------------------------------------------------------


def attn_forward(cfg: ModelConfig, p, x, *, causal=True, window=None,
                 positions=None, mrope_pos=None):
    """Full-sequence self-attention. x: (B,S,D)."""
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.arange(S)[None, :]
    q = _project_q(cfg, p, x)
    k, v = _project_kv(cfg, p, x)
    q, k = _positions(cfg, q, k, positions, positions, mrope_pos)
    out = chunked_attention(cfg, q, k, v, causal=causal, window=window)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"])


def cross_attn_forward(cfg: ModelConfig, p, x, enc_k, enc_v):
    """Cross-attention against precomputed encoder K/V (no positions)."""
    q = _project_q(cfg, p, x)
    mask = jnp.ones((q.shape[1], enc_k.shape[1]), bool)
    out = _attend_dense(cfg, q, enc_k, enc_v, mask)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"])


def encode_kv(cfg: ModelConfig, p, x_enc):
    """Precompute cross-attention K/V from encoder output."""
    return _project_kv(cfg, p, x_enc)


# -- prefill: same as forward but also returns the KV cache ---------------


def attn_prefill(cfg: ModelConfig, p, x, cache_len: int, *, causal=True,
                 window=None, positions=None, mrope_pos=None):
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.arange(S)[None, :]
    q = _project_q(cfg, p, x)
    k, v = _project_kv(cfg, p, x)
    q, k = _positions(cfg, q, k, positions, positions, mrope_pos)
    out = chunked_attention(cfg, q, k, v, causal=causal, window=window)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    K, hd = cfg.num_kv_heads, cfg.head_dim
    ck = jnp.zeros((B, cache_len, K, hd), k.dtype).at[:, :S].set(k)
    cv = jnp.zeros((B, cache_len, K, hd), v.dtype).at[:, :S].set(v)
    return y, (ck, cv)


# -- decode: one new token against the cache -------------------------------


def attn_decode(cfg: ModelConfig, p, x, cache: Tuple, pos, *, window=None,
                mrope_pos=None, layer=None):
    """x: (B,1,D); cache (ck, cv): (B,Smax,K,hd), or with ``layer`` (a
    scalar int32) the (L,B,Smax,K,hd) stacks over layers, written at that
    layer in place and read there; pos: scalar int32.

    Returns (y, new_cache). The attention over the cache is the jnp oracle
    for kernels/decode_attention.
    """
    ck, cv = cache
    B, Smax, K, hd = ck.shape[-4:]
    q = _project_q(cfg, p, x)
    k, v = _project_kv(cfg, p, x)
    pos_b = jnp.full((B, 1), pos)
    q, k = _positions(cfg, q, k, pos_b, pos_b, mrope_pos)
    from repro.sharding import rules as _rules_upd  # noqa: F401 (registers update rules)
    from repro.sharding.constraints import _current_mesh as _cm

    _mesh_upd = _cm()
    if _mesh_upd is not None:
        # Mask-based update: a dynamic-update-slice at a traced position
        # into a sequence-sharded cache forces GSPMD to replicate the whole
        # cache (observed +134 MB/layer); a where() is elementwise-local.
        sel = (jnp.arange(Smax) == pos)[None, :, None, None]
        ck = jnp.where(sel, k.astype(ck.dtype), ck)
        cv = jnp.where(sel, v.astype(cv.dtype), cv)
    elif layer is None:
        ck = jax.lax.dynamic_update_slice_in_dim(ck, k, pos, axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(cv, v, pos, axis=1)
    else:
        at = (layer, 0, pos, 0, 0)
        ck = jax.lax.dynamic_update_slice(ck, k[None].astype(ck.dtype), at)
        cv = jax.lax.dynamic_update_slice(cv, v[None].astype(cv.dtype), at)
    # this layer's (B,Smax,K,hd) keys and values
    if layer is None:
        lk, lv = ck, cv
    else:
        lk, lv = (jax.lax.dynamic_index_in_dim(c, layer, 0, keepdims=False)
                  for c in (ck, cv))
    ki = jnp.arange(Smax)
    valid = ki <= pos
    if window is not None:
        valid &= ki > pos - window
    H = cfg.num_heads
    from repro.sharding.constraints import mesh_axis_size

    from repro.sharding import rules as _rules
    from repro.sharding.constraints import _current_mesh

    # Pallas decode-attention kernel (single-device serving path).
    from repro.kernels import decode_attention as _dec
    from repro.kernels import ops as _ops

    if (_ops.use_pallas() and _current_mesh() is None
            and Smax % _dec.BLOCK_K == 0
            and H % K == 0 and not cfg.mrope_sections):
        if layer is not None:
            # The stack stays in its row-major layout: the kernel's
            # (B,K,S,hd) operand is staged one layer at a time, where the
            # compiler would otherwise relayout the whole stack on the way
            # in and out of the step.
            from jax.experimental.layout import Layout, with_layout_constraint

            lk, lv = (with_layout_constraint(x, Layout(tuple(range(x.ndim))))
                      for x in (lk, lv))
        out = _ops.decode_attention(
            q[:, 0], lk.transpose(0, 2, 1, 3), lv.transpose(0, 2, 1, 3), pos,
            window=window, softcap=cfg.attn_logit_softcap)[:, None]
        y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
        return y, (ck, cv)

    ke, ve = lk, lv
    if K != H:
        ke = jnp.repeat(lk, H // K, axis=2)
        ve = jnp.repeat(lv, H // K, axis=2)
    msize = mesh_axis_size("model")
    mesh = _current_mesh()
    seq_layout = (mesh is not None
                  and _rules.decode_kv_plan(B, K, mesh, H) == "seq")
    heads_ok = (not seq_layout) and msize > 0 and H % msize == 0
    if seq_layout:
        # Flash-decode layout: KV sequence sharded over `model`; softmax
        # max/sum and the (B,H,hd) output are the only cross-shard
        # reductions (§Perf iteration, decode pairs).
        ke = constrain(ke, "batch", "kv_seq", None, None)
        ve = constrain(ve, "batch", "kv_seq", None, None)
    elif heads_ok:
        ke = constrain(ke, "batch", "seq", "heads", None)
        ve = constrain(ve, "batch", "seq", "heads", None)
    qh = q[:, 0]                                        # (B,H,hd)
    logits = jnp.einsum("bhk,bthk->bht", qh, ke).astype(jnp.float32)
    if seq_layout:
        logits = constrain(logits, "batch", None, "kv_seq")
    elif heads_ok:
        logits = constrain(logits, "batch", "heads", None)
    else:
        logits = constrain(logits, "batch", None, "kv_seq")
    logits = _softcap(logits * (hd ** -0.5), cfg.attn_logit_softcap)
    logits = jnp.where(valid[None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(cv.dtype)
    out = jnp.einsum("bht,bthk->bhk", probs, ve)[:, None]  # (B,1,H,hd)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return y, (ck, cv)


def cross_attn_decode(cfg: ModelConfig, p, x, enc_kv):
    enc_k, enc_v = enc_kv
    q = _project_q(cfg, p, x)
    mask = jnp.ones((1, enc_k.shape[1]), bool)
    out = _attend_dense(cfg, q, enc_k, enc_v, mask)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"])
