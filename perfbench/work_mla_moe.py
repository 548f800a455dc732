"""Operations and bytes of latent attention and the held-expert layer
(DeepSeek-V2 keys, ``n_routed_experts`` the experts held on this chip),
from the shapes of the work as ``work.py`` counts them: cache positions
past a row's own, padding rows of the grouped matmul and re-reads of
weights are not counted.
"""
from __future__ import annotations

from typing import Dict, Sequence, Tuple

ELEM = 2   # bfloat16


def mla_dims(m: Dict) -> Tuple[int, int, int, int, int, int]:
    """(layers, heads, rank, nope, rope, v)."""
    return (m["num_hidden_layers"], m["num_attention_heads"],
            m["kv_lora_rank"], m["qk_nope_head_dim"], m["qk_rope_head_dim"],
            m["v_head_dim"])


def moe_layers(m: Dict) -> int:
    return m["num_hidden_layers"] - m["first_k_dense_replace"]


def mla_decode_work(m: Dict, contexts: Sequence[int],
                    elem_bytes: int = ELEM) -> Tuple[float, float]:
    """Absorbed decode attention, one entry of ``contexts`` per decode row
    (positions attended, its own included), all layers: each head's scores
    over the (rank + rope)-wide latent and its weighted sum of the
    rank-wide latents; the row's latents read once for all heads, and its
    queries."""
    L, H, r, _, rope, _ = mla_dims(m)
    W = r + rope
    flops = nbytes = 0.0
    for c in contexts:
        flops += 2 * H * c * (W + r)
        nbytes += (c * W + H * W) * elem_bytes
    return L * flops, L * nbytes


def expert_bytes(m: Dict, elem_bytes: int = ELEM) -> float:
    return 3 * m["hidden_size"] * m["moe_intermediate_size"] * elem_bytes


def moe_gmm_work(m: Dict, held_rows: float, calls: int,
                 elem_bytes: int = ELEM) -> Tuple[float, float]:
    """The grouped expert matmul over ``held_rows`` rows routed to held
    experts in ``calls`` model steps (each running every expert layer
    once): a SwiGLU of 3 matrices per row; each step reads the weights of
    the held experts its rows touch, at most one per row of the step
    (rows spread evenly over the steps and layers), and every row in and
    out once."""
    D, F = m["hidden_size"], m["moe_intermediate_size"]
    n = moe_layers(m)
    flops = 6.0 * D * F * held_rows
    if calls <= 0 or n <= 0:
        return flops, 0.0
    touched = min(m["n_routed_experts"], held_rows / (n * calls))
    nbytes = (calls * n * touched * expert_bytes(m, elem_bytes)
              + held_rows * 2 * D * elem_bytes)
    return flops, nbytes


def _proj_flops(m: Dict) -> Tuple[float, float]:
    """Per token and attention layer: projections of the expanded form
    (prefill) and of the absorbed form (decode)."""
    D = m["hidden_size"]
    _, H, r, nope, rope, v = mla_dims(m)
    common = 2 * D * H * (nope + rope) + 2 * D * (r + rope) + 2 * H * v * D
    expanded = common + 2 * r * H * (nope + v)
    absorbed = common + 2 * H * nope * r + 2 * H * r * v
    return expanded, absorbed


def model_flops(m: Dict, experts: int, prompt_lens: Sequence[int],
                decode_contexts: Sequence[int], held_rows: float) -> float:
    """This chip's model FLOPs: every prompt token through expanded
    attention (causal pairs) and every useful decode row through absorbed
    attention, the dense layer, the shared experts and the router on
    every token, the ``held_rows`` rows routed to held experts (prompt and
    decode), and the unembedding of each prompt's last position and each
    decode row. ``experts``: the router's width (all routed experts)."""
    D, V = m["hidden_size"], m["vocab_size"]
    L, H, r, nope, rope, v = mla_dims(m)
    n_moe = moe_layers(m)
    n_dense = m["first_k_dense_replace"]
    expanded, absorbed = _proj_flops(m)
    ffn = (n_dense * 6 * D * m["intermediate_size"]
           + n_moe * (6 * D * m["n_shared_experts"] * m["moe_intermediate_size"]
                      + 2 * D * experts))
    n_prompt = sum(prompt_lens)
    attn_prefill = sum(2 * H * (nope + rope + v) * Lp * (Lp + 1) / 2
                       for Lp in prompt_lens)
    attn_decode, _ = mla_decode_work(m, decode_contexts)
    routed = 6.0 * D * m["moe_intermediate_size"] * held_rows
    unembed = 2 * D * V * (len(prompt_lens) + len(decode_contexts))
    return (n_prompt * (L * expanded + ffn) + L * attn_prefill
            + len(decode_contexts) * (L * absorbed + ffn) + attn_decode
            + routed + unembed)
