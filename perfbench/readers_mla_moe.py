"""What the latent-attention and expert-layer readers in ``metrics/`` share:
each returns ``None`` on a record of another path or model."""
from __future__ import annotations

from typing import Dict, Optional

from perfbench import work_mla_moe
from perfbench.readers import (decode_contexts, kernel_roofline, mfu,
                               module_ms, of_path, prompt_lens)


def of_mla_moe(rec: Dict) -> bool:
    return of_path(rec, "serving") and "deployment" in rec


def held_rows(rec: Dict) -> float:
    """Rows routed to held experts in the window's batches (prompt and
    decode), from the engine's counter."""
    return sum(b["moe_rows_held"] for b in rec["batches"])


def model_steps(rec: Dict) -> int:
    """Prefill and decode steps of the window's batches."""
    return sum(max(b["outs"]) for b in rec["batches"])


def mla_decode_roofline(rec: Dict) -> Optional[float]:
    if not of_mla_moe(rec):
        return None
    flops, nbytes = work_mla_moe.mla_decode_work(rec["model"],
                                                 decode_contexts(rec))
    return kernel_roofline(rec, "mla_decode_attention", flops, nbytes)


def moe_gmm_roofline(rec: Dict) -> Optional[float]:
    if not of_mla_moe(rec):
        return None
    flops, nbytes = work_mla_moe.moe_gmm_work(rec["model"], held_rows(rec),
                                              model_steps(rec))
    return kernel_roofline(rec, "moe_gmm", flops, nbytes)


def decode_step_ms(rec: Dict) -> Optional[float]:
    return module_ms(rec, "mla_decode_attention") if of_mla_moe(rec) else None


def serve_mfu(rec: Dict) -> Optional[float]:
    if not of_mla_moe(rec) or not rec.get("trace"):
        return None
    flops = work_mla_moe.model_flops(
        rec["model"], rec["deployment"]["n_routed_experts"], prompt_lens(rec),
        decode_contexts(rec), held_rows(rec))
    return mfu(rec, flops, rec["trace"]["window_s"])
