"""Model assembly: layer-pattern stacks scanned with lax.scan.

The layer stack is ``pattern * repeats + suffix`` (configs/base.py). Params
for each pattern position are stacked with a leading ``repeats`` dim and the
stack is driven by one ``lax.scan`` — HLO size stays O(pattern), not
O(num_layers), which keeps 62-layer compiles cheap and is also what the
green partitioner reasons over.

Public API:
    model_spec / init_params / abstract_params / logical_axes
    forward(cfg, params, batch)           -> (hidden, aux)    full sequence
    unembed(cfg, params, hidden)          -> logits
    init_cache / abstract_cache
    prefill(cfg, params, batch, max_len)  -> (cache, last_hidden)
    decode_step(cfg, params, cache, token, pos) -> (logits, cache)

Decode on one device carries each stack's attention caches (K/V, MLA
latent) through its layer scan and writes one position a layer in place;
the serving engine donates the cache, so a token never copies it whole.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import LayerDef, ModelConfig
from repro.models import attention, common, mla, mlp, modes, moe, ssm, xlstm
from repro.models.common import ParamSpec
from repro.sharding.constraints import constrain

PyTree = Any


# ---------------------------------------------------------------------------
# Specs
# ---------------------------------------------------------------------------


def _ffn_spec(cfg: ModelConfig, ld: LayerDef) -> Dict:
    D = cfg.d_model
    if cfg.is_moe_layer(ld):
        return {"ln2": common.norm_spec(cfg, D), "moe": moe.moe_spec(cfg)}
    if cfg.d_ff > 0:
        return {"ln2": common.norm_spec(cfg, D),
                "mlp": mlp.mlp_spec(cfg, cfg.d_ff, cfg.mlp_gated)}
    return {}


def block_spec(cfg: ModelConfig, ld: LayerDef, decoder: bool) -> Dict:
    D = cfg.d_model
    if ld.kind == "attn":
        spec = {"ln1": common.norm_spec(cfg, D), "attn": attention.attention_spec(cfg)}
        if decoder and cfg.cross_attention:
            spec["ln_x"] = common.norm_spec(cfg, D)
            spec["xattn"] = attention.attention_spec(cfg)
        spec.update(_ffn_spec(cfg, ld))
        return spec
    if ld.kind == "mla":
        return {"ln1": common.norm_spec(cfg, D), "attn": mla.mla_spec(cfg),
                **_ffn_spec(cfg, ld)}
    if ld.kind == "mamba2":
        return {"ln1": common.norm_spec(cfg, D), "mamba": ssm.mamba2_spec(cfg)}
    if ld.kind == "mlstm":
        return {"ln1": common.norm_spec(cfg, D), "mlstm": xlstm.mlstm_spec(cfg)}
    if ld.kind == "slstm":
        return {"ln1": common.norm_spec(cfg, D), "slstm": xlstm.slstm_spec(cfg)}
    raise ValueError(ld.kind)


def model_spec(cfg: ModelConfig) -> Dict:
    D, V = cfg.d_model, cfg.vocab_size
    spec: Dict = {
        "embedding": {"table": ParamSpec((V, D), ("vocab", "embed"), scale=0.02)},
        "final_norm": common.norm_spec(cfg, D),
    }
    if not cfg.tie_embeddings:
        spec["lm_head"] = ParamSpec((D, V), ("embed", "vocab"))
    if cfg.prefix:
        spec["prefix"] = common.stack_spec(
            block_spec(cfg, cfg.prefix[0], decoder=True), len(cfg.prefix))
    # pattern positions, each stacked over repeats
    spec["pattern"] = {
        str(i): common.stack_spec(block_spec(cfg, ld, decoder=True), cfg.repeats)
        for i, ld in enumerate(cfg.pattern)
    }
    if cfg.suffix:
        spec["suffix"] = common.stack_spec(
            block_spec(cfg, cfg.suffix[0], decoder=True), len(cfg.suffix))
    if cfg.encoder_layers:
        spec["encoder"] = common.stack_spec(
            _encoder_block_spec(cfg), cfg.encoder_layers)
        spec["encoder_norm"] = common.norm_spec(cfg, D)
    return spec


def _encoder_block_spec(cfg: ModelConfig) -> Dict:
    D = cfg.d_model
    return {
        "ln1": common.norm_spec(cfg, D),
        "attn": attention.attention_spec(cfg),
        "ln2": common.norm_spec(cfg, D),
        "mlp": mlp.mlp_spec(cfg, cfg.d_ff, cfg.mlp_gated),
    }


def init_params(cfg: ModelConfig, key: jax.Array) -> PyTree:
    return common.init_from_spec(model_spec(cfg), key, jnp.dtype(cfg.param_dtype))


def abstract_params(cfg: ModelConfig) -> PyTree:
    return common.abstract_from_spec(model_spec(cfg), jnp.dtype(cfg.param_dtype))


def logical_axes(cfg: ModelConfig) -> PyTree:
    return common.axes_from_spec(model_spec(cfg))


# ---------------------------------------------------------------------------
# Block forward (full sequence)
# ---------------------------------------------------------------------------


def _ffn(cfg: ModelConfig, p, h):
    """Feed-forward sub-block with its residual: (h, aux, rows), ``rows``
    the (n_held,) count of rows routed to held experts (None for a dense
    feed-forward)."""
    aux = jnp.zeros((), jnp.float32)
    if "moe" in p:
        y, aux, rows = moe.moe_apply(cfg, p["moe"], common.apply_norm(cfg, p["ln2"], h))
        return h + y, aux, rows
    if "mlp" in p:
        h = h + mlp.mlp_forward(cfg, p["mlp"], common.apply_norm(cfg, p["ln2"], h),
                                cfg.mlp_gated)
    return h, aux, None


def _block_forward(cfg: ModelConfig, ld: LayerDef, p, h, ctx) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Residual block. ctx: dict(positions, mrope_pos, enc_kv_fn, causal)."""
    aux = jnp.zeros((), jnp.float32)
    if ld.kind == "attn":
        h = h + attention.attn_forward(
            cfg, p["attn"], common.apply_norm(cfg, p["ln1"], h),
            causal=ctx.get("causal", True), window=ld.window,
            positions=ctx.get("positions"), mrope_pos=ctx.get("mrope_pos"))
        if "xattn" in p and ctx.get("enc_out") is not None:
            xn = common.apply_norm(cfg, p["ln_x"], h)
            ek, ev = attention.encode_kv(cfg, p["xattn"], ctx["enc_out"])
            h = h + attention.cross_attn_forward(cfg, p["xattn"], xn, ek, ev)
        h, aux, _ = _ffn(cfg, p, h)
    elif ld.kind == "mla":
        h = h + mla.mla_forward(cfg, p["attn"], common.apply_norm(cfg, p["ln1"], h),
                                positions=ctx.get("positions"))
        h, aux, _ = _ffn(cfg, p, h)
    elif ld.kind == "mamba2":
        h = h + ssm.mamba2_forward(cfg, p["mamba"], common.apply_norm(cfg, p["ln1"], h))
    elif ld.kind == "mlstm":
        h = h + xlstm.mlstm_forward(cfg, p["mlstm"], common.apply_norm(cfg, p["ln1"], h))
    elif ld.kind == "slstm":
        h = h + xlstm.slstm_forward(cfg, p["slstm"], common.apply_norm(cfg, p["ln1"], h))
    else:
        raise ValueError(ld.kind)
    return h, aux


def _scan_blocks(cfg: ModelConfig, defs, stacked_params, h, ctx):
    """Scan the repeating unit over its stacked params."""

    def body(carry, xs):
        hh, aux_sum = carry
        for i, ld in enumerate(defs):
            hh, aux = _block_forward(cfg, ld, xs[str(i)], hh, ctx)
            hh = constrain(hh, "batch", None, None)
            aux_sum = aux_sum + aux
        return (hh, aux_sum), None

    body_fn = jax.checkpoint(body) if cfg.remat else body
    (h, aux), _ = modes.scan(body_fn, (h, jnp.zeros((), jnp.float32)), stacked_params)
    return h, aux


# ---------------------------------------------------------------------------
# Embedding / unembedding / inputs
# ---------------------------------------------------------------------------


def embed(cfg: ModelConfig, params, tokens):
    h = params["embedding"]["table"].astype(jnp.dtype(cfg.dtype))[tokens]
    return constrain(h, "batch", None, None)


def unembed(cfg: ModelConfig, params, h):
    if cfg.tie_embeddings:
        return jnp.einsum("...d,vd->...v", h, params["embedding"]["table"])
    return jnp.einsum("...d,dv->...v", h, params["lm_head"])


def _assemble_inputs(cfg: ModelConfig, params, batch):
    """Returns (h, ctx) for the decoder stack."""
    tokens = batch["tokens"]
    B = tokens.shape[0]
    h = embed(cfg, params, tokens)
    ctx: Dict = {"causal": True}
    if cfg.vision_tokens:
        ve = batch["vision_embeds"].astype(h.dtype)
        h = jnp.concatenate([ve, h], axis=1)
    S = h.shape[1]
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    ctx["positions"] = positions
    if cfg.mrope_sections:
        mp = batch.get("mrope_positions")
        if mp is None:
            mp = jnp.broadcast_to(positions[:, None, :], (B, 3, S))
        ctx["mrope_pos"] = mp
    if cfg.pos_emb == "sinusoidal":
        h = h + common.sinusoidal_pos_emb(positions, cfg.d_model).astype(h.dtype)
    if cfg.encoder_layers:
        ctx["enc_out"] = encode(cfg, params, batch["encoder_embeds"])
    return h, ctx


def encode(cfg: ModelConfig, params, enc_embeds):
    """Whisper-style encoder over stub frame embeddings."""
    B, S, _ = enc_embeds.shape
    h = enc_embeds.astype(jnp.dtype(cfg.dtype))
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    if cfg.pos_emb == "sinusoidal":
        h = h + common.sinusoidal_pos_emb(pos, cfg.d_model).astype(h.dtype)

    def body(carry, xs):
        hh = carry
        hh = hh + attention.attn_forward(
            cfg, xs["attn"], common.apply_norm(cfg, xs["ln1"], hh),
            causal=False, window=None, positions=pos)
        hh = hh + mlp.mlp_forward(cfg, xs["mlp"],
                                  common.apply_norm(cfg, xs["ln2"], hh), cfg.mlp_gated)
        return hh, None

    body_fn = jax.checkpoint(body) if cfg.remat else body
    h, _ = modes.scan(body_fn, h, params["encoder"])
    return common.apply_norm(cfg, params["encoder_norm"], h)


# ---------------------------------------------------------------------------
# Full-sequence forward (train / eval)
# ---------------------------------------------------------------------------


def forward(cfg: ModelConfig, params, batch) -> Tuple[jnp.ndarray, jnp.ndarray]:
    h, ctx = _assemble_inputs(cfg, params, batch)
    if cfg.prefix:
        h, aux0 = _scan_blocks(cfg, (cfg.prefix[0],), {"0": params["prefix"]},
                               h, ctx)
    h, aux = _scan_blocks(cfg, cfg.pattern, params["pattern"], h, ctx)
    if cfg.prefix:
        aux = aux + aux0
    if cfg.suffix:
        h, aux2 = _scan_blocks(cfg, (cfg.suffix[0],), {"0": params["suffix"]},
                               h, ctx)
        aux = aux + aux2
    h = common.apply_norm(cfg, params["final_norm"], h)
    return h, aux


# ---------------------------------------------------------------------------
# KV / state caches
# ---------------------------------------------------------------------------
#
# The cache holds one entry per stack ("prefix", "pattern" by position,
# "suffix"), stacked over that stack's layers, and, in a model with
# experts, "moe_rows": (expert layers, n_held) int32 rows routed to each
# held expert, accumulated by prefill and every decode step.


def _stacks(cfg: ModelConfig):
    """(cache/param key, layer defs of one scan step) in layer order."""
    out = [("prefix", (cfg.prefix[0],))] if cfg.prefix else []
    out.append(("pattern", cfg.pattern))
    if cfg.suffix:
        out.append(("suffix", (cfg.suffix[0],)))
    return out


def _by_position(name, tree):
    """A stack's params or cache keyed by pattern position (prefix and
    suffix have one)."""
    return tree if name == "pattern" else {"0": tree}


def _scan_params(cfg: ModelConfig, name, defs, stacked):
    """The stack's params to scan, by position, and the routed experts'
    weights of its expert layers kept whole: on one chip the grouped matmul
    reads the layer's experts from the stacked weights by index, so the
    scan makes no per-layer copy of them."""
    from repro.sharding.constraints import _current_mesh

    tree = _by_position(name, stacked)
    if cfg.moe is None or _current_mesh() is not None:
        return tree, {}
    xs, whole = {}, {}
    for i, ld in enumerate(defs):
        p = tree[str(i)]
        if cfg.is_moe_layer(ld):
            whole[str(i)] = {k: p["moe"][k] for k in moe.EXPERT_WEIGHTS}
            p = dict(p, moe={k: v for k, v in p["moe"].items()
                             if k not in moe.EXPERT_WEIGHTS})
        xs[str(i)] = p
    return xs, whole


def _layer_index(stacked, whole):
    """The layer index scanned beside the params where a layer's experts
    are kept whole (None, no scan input, elsewhere)."""
    return jnp.arange(jax.tree.leaves(stacked)[0].shape[0]) if whole else None


def _layer_params(p, whole, layer):
    """Layer ``layer``'s params at one position of a scan step."""
    if whole is None:
        return p
    return dict(p, moe=dict(p["moe"], layer=layer, **whole))


def _block_cache(cfg: ModelConfig, ld: LayerDef, batch: int, max_len: int, dtype):
    if ld.kind == "attn":
        K, hd = cfg.num_kv_heads, cfg.head_dim
        c = {"k": jnp.zeros((batch, max_len, K, hd), dtype),
             "v": jnp.zeros((batch, max_len, K, hd), dtype)}
        if cfg.cross_attention:
            c["xk"] = jnp.zeros((batch, cfg.encoder_seq, K, hd), dtype)
            c["xv"] = jnp.zeros((batch, cfg.encoder_seq, K, hd), dtype)
        return c
    if ld.kind == "mla":
        return {"latent": jnp.zeros((batch, max_len, cfg.mla.latent_dim), dtype)}
    if ld.kind == "mamba2":
        return ssm.mamba2_init_cache(cfg, batch, dtype)
    if ld.kind == "mlstm":
        return xlstm.mlstm_init_cache(cfg, batch, dtype)
    if ld.kind == "slstm":
        return xlstm.slstm_init_cache(cfg, batch, dtype)
    raise ValueError(ld.kind)


def _stack_cache(tree, n):
    return jax.tree.map(lambda x: jnp.broadcast_to(x[None], (n,) + x.shape), tree)


def moe_layers(cfg: ModelConfig) -> int:
    return sum(cfg.is_moe_layer(ld) for ld in cfg.layer_defs)


def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> PyTree:
    dtype = jnp.dtype(cfg.dtype)
    cache: Dict = {"pattern": {
        str(i): _stack_cache(_block_cache(cfg, ld, batch, max_len, dtype), cfg.repeats)
        for i, ld in enumerate(cfg.pattern)
    }}
    if cfg.prefix:
        cache["prefix"] = _stack_cache(
            _block_cache(cfg, cfg.prefix[0], batch, max_len, dtype), len(cfg.prefix))
    if cfg.suffix:
        cache["suffix"] = _stack_cache(
            _block_cache(cfg, cfg.suffix[0], batch, max_len, dtype), len(cfg.suffix))
    if cfg.moe is not None:
        cache["moe_rows"] = jnp.zeros((moe_layers(cfg), cfg.moe.n_held), jnp.int32)
    return cache


def abstract_cache(cfg: ModelConfig, batch: int, max_len: int) -> PyTree:
    return jax.eval_shape(lambda: init_cache(cfg, batch, max_len))


def _gather_rows(cfg: ModelConfig, rows_by_stack):
    """Concatenate the per-stack (layers, n_held) counts in layer order
    (prefix, pattern positions, suffix)."""
    parts = [rows_by_stack[name][i] for name, defs in _stacks(cfg)
             for i, ld in enumerate(defs) if cfg.is_moe_layer(ld)]
    return jnp.concatenate(parts, axis=0)


# The cache entries of each layer kind that one decode token writes one
# position of. On one device the decode step carries them through the layer
# scan, stacked over the stack's layers, and writes that position in place;
# every other entry (recurrent state, cross-attention K/V) is small and
# rewritten whole, so it is scanned per layer.
CARRIED = {"attn": ("k", "v"), "mla": ("latent",)}


def cache_in_place() -> bool:
    """Whether ``decode_step`` carries the attention caches and updates them
    in place: on one device. Under a mesh (the dry-run and GSPMD paths) the
    sequence-sharded cache takes the mask update, so each layer's cache is
    scanned in and out whole."""
    from repro.sharding.constraints import _current_mesh

    return _current_mesh() is None


# ---------------------------------------------------------------------------
# Prefill
# ---------------------------------------------------------------------------

# Prompt tokens one prefill pass takes at once: a larger batch is prefilled
# in row chunks inside the same jitted step, each writing its rows of the
# cache, so its activations stay within what the chip holds beside the
# weights and the whole cache.
PREFILL_ROW_TOKENS = 16384


def _block_prefill(cfg, ld, p, h, ctx, max_len):
    """Returns (h, cache entry, held-expert rows or None)."""
    if ld.kind == "attn":
        y, (ck, cv) = attention.attn_prefill(
            cfg, p["attn"], common.apply_norm(cfg, p["ln1"], h), max_len,
            causal=True, window=ld.window,
            positions=ctx.get("positions"), mrope_pos=ctx.get("mrope_pos"))
        h = h + y
        c = {"k": ck, "v": cv}
        if "xattn" in p and ctx.get("enc_out") is not None:
            xn = common.apply_norm(cfg, p["ln_x"], h)
            ek, ev = attention.encode_kv(cfg, p["xattn"], ctx["enc_out"])
            h = h + attention.cross_attn_forward(cfg, p["xattn"], xn, ek, ev)
            c["xk"], c["xv"] = ek, ev
        h, _, rows = _ffn(cfg, p, h)
        return h, c, rows
    if ld.kind == "mla":
        y, lat = mla.mla_prefill(cfg, p["attn"], common.apply_norm(cfg, p["ln1"], h),
                                 max_len, positions=ctx.get("positions"))
        h, _, rows = _ffn(cfg, p, h + y)
        return h, {"latent": lat}, rows
    if ld.kind == "mamba2":
        y, c = ssm.mamba2_prefill(cfg, p["mamba"], common.apply_norm(cfg, p["ln1"], h))
        return h + y, c, None
    if ld.kind == "mlstm":
        y, c = xlstm.mlstm_forward(cfg, p["mlstm"],
                                   common.apply_norm(cfg, p["ln1"], h), return_state=True)
        return h + y, c, None
    if ld.kind == "slstm":
        y, c = xlstm.slstm_forward(cfg, p["slstm"],
                                   common.apply_norm(cfg, p["ln1"], h), return_state=True)
        return h + y, c, None
    raise ValueError(ld.kind)


def _prefill_all(cfg: ModelConfig, params, batch, max_len: int):
    h, ctx = _assemble_inputs(cfg, params, batch)
    cache, rows = {}, {}
    for name, defs in _stacks(cfg):
        xs, whole = _scan_params(cfg, name, defs, params[name])

        def body(hh, xs, defs=defs, whole=whole, name=name):
            p, layer = xs
            caches, counts = {}, {}
            for i, ld in enumerate(defs):
                pi = _layer_params(p[str(i)], whole.get(str(i)), layer)
                hh, c, r = _block_prefill(cfg, ld, pi, hh, ctx, max_len)
                caches[str(i)] = c
                if r is not None:
                    counts[i] = r
            return hh, (caches if name == "pattern" else caches["0"], counts)

        h, (cache[name], rows[name]) = modes.scan(
            body, h, (xs, _layer_index(params[name], whole)))
    if cfg.moe is not None:
        cache["moe_rows"] = _gather_rows(cfg, rows)
    h = common.apply_norm(cfg, params["final_norm"], h)
    return cache, h[:, -1]


def _prefill_rows(cfg: ModelConfig, batch) -> int:
    """Rows per prefill pass: the whole batch, or the largest divisor of
    it whose prompts hold at most ``PREFILL_ROW_TOKENS`` tokens (token-only
    batches)."""
    B, S = batch["tokens"].shape
    if B * S <= PREFILL_ROW_TOKENS or set(batch) != {"tokens"}:
        return B
    return max(r for r in range(1, B + 1)
               if B % r == 0 and r * S <= max(PREFILL_ROW_TOKENS, S))


def prefill(cfg: ModelConfig, params, batch, max_len: int):
    """Run the prompt, build the cache. Returns (cache, last_hidden)."""
    tokens = batch["tokens"]
    B = tokens.shape[0]
    rows = _prefill_rows(cfg, batch)
    if rows == B:
        return _prefill_all(cfg, params, batch, max_len)

    def body(i, carry):
        cache, last = carry
        sub = jax.lax.dynamic_slice_in_dim(tokens, i * rows, rows, axis=0)
        c, h = _prefill_all(cfg, params, {"tokens": sub}, max_len)
        cache = {k: (cache[k] + c[k] if k == "moe_rows" else jax.tree.map(
            lambda full, part: jax.lax.dynamic_update_slice_in_dim(
                full, part, i * rows, axis=1), cache[k], c[k]))
            for k in cache}
        return cache, jax.lax.dynamic_update_slice_in_dim(last, h, i * rows, axis=0)

    last = jnp.zeros((B, cfg.d_model), jnp.dtype(cfg.dtype))
    return jax.lax.fori_loop(0, B // rows, body,
                             (init_cache(cfg, B, max_len), last))


# ---------------------------------------------------------------------------
# Decode (one token)
# ---------------------------------------------------------------------------


def _block_decode(cfg, ld, p, c, h, pos, ctx, layer=None):
    """Returns (h, cache entry, held-expert rows or None). With ``layer``,
    the entry's ``CARRIED`` arrays are the whole stack's, read and written
    at that layer; the rest are the layer's own."""
    if ld.kind == "attn":
        xn = common.apply_norm(cfg, p["ln1"], h)
        mrope = None
        if cfg.mrope_sections:
            B = h.shape[0]
            mrope = jnp.broadcast_to(jnp.asarray(pos)[None, None, None], (B, 3, 1))
        y, (ck, cv) = attention.attn_decode(
            cfg, p["attn"], xn, (c["k"], c["v"]), pos, window=ld.window,
            mrope_pos=mrope, layer=layer)
        h = h + y
        c = dict(c, k=ck, v=cv)
        if "xattn" in p and "xk" in c:
            xn = common.apply_norm(cfg, p["ln_x"], h)
            h = h + attention.cross_attn_decode(cfg, p["xattn"], xn, (c["xk"], c["xv"]))
        h, _, rows = _ffn(cfg, p, h)
        return h, c, rows
    if ld.kind == "mla":
        y, lat = mla.mla_decode(cfg, p["attn"], common.apply_norm(cfg, p["ln1"], h),
                                c["latent"], pos, layer)
        h, _, rows = _ffn(cfg, p, h + y)
        return h, {"latent": lat}, rows
    if ld.kind == "mamba2":
        y, c = ssm.mamba2_decode(cfg, p["mamba"], common.apply_norm(cfg, p["ln1"], h), c)
        return h + y, c, None
    if ld.kind == "mlstm":
        y, c = xlstm.mlstm_decode(cfg, p["mlstm"], common.apply_norm(cfg, p["ln1"], h), c)
        return h + y, c, None
    if ld.kind == "slstm":
        y, c = xlstm.slstm_decode(cfg, p["slstm"], common.apply_norm(cfg, p["ln1"], h), c)
        return h + y, c, None
    raise ValueError(ld.kind)


def _split_carried(defs, c):
    """A stack's cache by position -> (``CARRIED`` entries, the rest)."""
    carried, rest = {}, {}
    for i, ld in enumerate(defs):
        keys = CARRIED.get(ld.kind, ())
        carried[str(i)] = {k: v for k, v in c[str(i)].items() if k in keys}
        rest[str(i)] = {k: v for k, v in c[str(i)].items() if k not in keys}
    return carried, rest


def _decode_stack(cfg, name, defs, stacked, c, h, pos, ctx):
    """One stack's layers for one token, each layer's cache scanned in and
    out whole. Returns (h, the stack's new cache, held-expert rows)."""
    xs, whole = _scan_params(cfg, name, defs, stacked)

    def body(hh, xs):
        p, c, layer = xs
        c = _by_position(name, c)
        new_c, counts = {}, {}
        for i, ld in enumerate(defs):
            pi = _layer_params(p[str(i)], whole.get(str(i)), layer)
            hh, nc, r = _block_decode(cfg, ld, pi, c[str(i)], hh, pos, ctx)
            new_c[str(i)] = nc
            if r is not None:
                counts[i] = r
        return hh, (new_c if name == "pattern" else new_c["0"], counts)

    h, (c, rows) = modes.scan(body, h, (xs, c, _layer_index(stacked, whole)))
    return h, c, rows


def _decode_stack_in_place(cfg, name, defs, stacked, c, h, pos, ctx):
    """``_decode_stack`` with the ``CARRIED`` entries in the scan's carry:
    each layer writes its one position into the stacked arrays, which are
    never sliced out or rebuilt whole."""
    xs, whole = _scan_params(cfg, name, defs, stacked)
    carried, rest = _split_carried(defs, _by_position(name, c))
    layers = jnp.arange(jax.tree.leaves(stacked)[0].shape[0])

    def body(carry, xs):
        hh, cc = carry
        p, rc, layer = xs
        entries, counts = {}, {}
        for i, ld in enumerate(defs):
            pi = _layer_params(p[str(i)], whole.get(str(i)), layer)
            hh, entries[str(i)], r = _block_decode(
                cfg, ld, pi, {**cc[str(i)], **rc[str(i)]}, hh, pos, ctx, layer)
            if r is not None:
                counts[i] = r
        cc, rc = _split_carried(defs, entries)
        return (hh, cc), (rc, counts)

    (h, carried), (rest, rows) = modes.scan(body, (h, carried),
                                            (xs, rest, layers))
    c = {i: {**carried[i], **rest[i]} for i in carried}
    return h, (c if name == "pattern" else c["0"]), rows


def decode_step(cfg: ModelConfig, params, cache, token, pos):
    """token: (B,1) int32; pos: scalar int32. Returns (logits (B,V), cache).

    On one device (``cache_in_place``) the attention caches (K/V, MLA
    latent) ride in each stack's layer scan as its carry and each layer
    writes its new position into them in place, so a token moves one
    position a layer of cache and never the whole of it; the serving engine
    donates the cache, so the step returns it in the same buffers. Under a
    mesh each layer's cache is scanned in and out whole."""
    h = embed(cfg, params, token)
    if cfg.pos_emb == "sinusoidal":
        h = h + common.sinusoidal_pos_emb(
            jnp.full((h.shape[0], 1), pos), cfg.d_model).astype(h.dtype)
    ctx: Dict = {}
    stack_fn = _decode_stack_in_place if cache_in_place() else _decode_stack
    new_cache, rows = {}, {}
    for name, defs in _stacks(cfg):
        h, new_cache[name], rows[name] = stack_fn(
            cfg, name, defs, params[name], cache[name], h, pos, ctx)
    if cfg.moe is not None:
        new_cache["moe_rows"] = cache["moe_rows"] + _gather_rows(cfg, rows)
    h = common.apply_norm(cfg, params["final_norm"], h)
    logits = unembed(cfg, params, h[:, 0])
    return logits, new_cache
