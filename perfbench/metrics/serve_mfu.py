"""Whole serving step's share of the chip's bf16 peak: model FLOPs of the
traced batches' useful prompt and output tokens over the traced window
times the peak."""
from perfbench import work
from perfbench.readers import decode_contexts, mfu, of_path, prompt_lens


def read(rec):
    if not of_path(rec, "serving") or not rec.get("trace"):
        return None
    flops = work.model_flops(rec["model"], prompt_lens(rec),
                             decode_contexts(rec))
    return mfu(rec, flops, rec["trace"]["window_s"])
