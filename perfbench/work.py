"""Operations and bytes that the algorithm needs, from the shapes of the
work, never from the shapes of today's implementation: padding, repeated
reads and cache slots past the position are not counted, so a change that
removes waste raises a share and can never push it past 100%.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, Sequence, Tuple

HERE = Path(__file__).resolve().parent

# Eq. 3/4 for one (task, node) cell from the raw columns: cpu and memory
# fractions (2 div, 2 min), S_R (2 mul, 1 add), S_L (1 sub), S_P (1 add,
# 1 div), S_B (1 mul, 1 add, 1 div), S_C (1 add, 1 div), the weighted sum
# (5 mul, 4 add), feasibility (4 compares, 3 and) and the running argmax
# (1 compare).
EQ3_FLOPS_PER_CELL = 32
# Per node, one scoring pass reads free cpu, free memory, load, average
# time, running tasks, intensity x E_est and the validity flag, as f32.
NODE_COLUMN_BYTES = 7 * 4
# Per task: the (cpu, mem_mb) profile in; the winner index and score out.
TASK_BYTES = 2 * 4 + 4 + 4


def peaks(device_kind: str) -> Dict[str, float]:
    """The peak table's row for ``device_kind``; an unknown device is an
    error, never a default."""
    with open(HERE / "peaks.json") as f:
        table = json.load(f)
    try:
        return table["devices"][device_kind]
    except KeyError:
        raise KeyError(f"device kind {device_kind!r} is not in peaks.json "
                       f"({sorted(table['devices'])})") from None


def least_time_s(flops: float, nbytes: float, pk: Dict[str, float]) -> float:
    """The larger of operations over peak FLOP/s and bytes over peak
    bytes/s."""
    return max(flops / pk["bf16_flops_per_s"], nbytes / pk["hbm_bytes_per_s"])


def select_work(rows: int, nodes: int, passes: int) -> Tuple[float, float]:
    """Scoring ``rows`` task profiles against ``nodes`` nodes in ``passes``
    engine steps: every (task, node) cell once, the node columns once per
    step, every task's profile and winner once."""
    flops = float(rows) * nodes * EQ3_FLOPS_PER_CELL
    nbytes = float(passes) * nodes * NODE_COLUMN_BYTES + float(rows) * TASK_BYTES
    return flops, nbytes


# -- transformer serving ------------------------------------------------------


def _attn_dims(m: Dict) -> Tuple[int, int, int, int]:
    return (m["num_hidden_layers"], m["num_attention_heads"],
            m["num_key_value_heads"], m["head_dim"])


def flash_attention_work(m: Dict, prompt_lens: Sequence[int],
                         elem_bytes: int = 2) -> Tuple[float, float]:
    """Causal prefill attention over each request's own prompt, all
    layers: QK^T and PV over the L(L+1)/2 causal pairs (2 FLOPs per
    multiply-add each); Q, K, V read and O written once."""
    layers, H, K, hd = _attn_dims(m)
    flops = nbytes = 0.0
    for L in prompt_lens:
        flops += 2 * 2 * H * hd * L * (L + 1) / 2
        nbytes += L * (2 * H + 2 * K) * hd * elem_bytes
    return layers * flops, layers * nbytes


def decode_attention_work(m: Dict, contexts: Sequence[int],
                          elem_bytes: int = 2) -> Tuple[float, float]:
    """One decode step per entry of ``contexts`` (positions attended,
    the new token's included), all layers: the query against that many
    cached keys and values, reading only those."""
    layers, H, K, hd = _attn_dims(m)
    flops = nbytes = 0.0
    for c in contexts:
        flops += 2 * 2 * H * hd * c
        nbytes += (2 * K * c + 2 * H) * hd * elem_bytes
    return layers * flops, layers * nbytes


def layer_matmul_params(m: Dict) -> int:
    """Weights one token multiplies through in the decoder stack."""
    D, H, K, hd = (m["hidden_size"], m["num_attention_heads"],
                   m["num_key_value_heads"], m["head_dim"])
    attn = D * H * hd * 2 + D * K * hd * 2
    mlp = 3 * D * m["intermediate_size"]
    return m["num_hidden_layers"] * (attn + mlp)


def model_flops(m: Dict, prompt_lens: Sequence[int],
                decode_contexts: Sequence[int]) -> float:
    """Model FLOPs of the useful tokens: every prompt token through the
    stack with causal attention, the unembedding of each prompt's last
    position (which yields the first output token), and each useful
    decode row through the stack, its attention and the unembedding."""
    P = layer_matmul_params(m)
    unembed = 2 * m["hidden_size"] * m["vocab_size"]
    attn_prefill, _ = flash_attention_work(m, prompt_lens)
    attn_decode, _ = decode_attention_work(m, decode_contexts)
    n_prompt = sum(prompt_lens)
    return (2 * P * n_prompt + attn_prefill + unembed * len(prompt_lens)
            + (2 * P + unembed) * len(decode_contexts) + attn_decode)
