"""Whole decode step's share of the chip's bf16 peak: model FLOPs of the
useful decode rows over the host time from each traced batch's first
decode call to its last token, times the peak."""
from perfbench import work
from perfbench.readers import decode_contexts, decode_phase_s, mfu, of_path


def read(rec):
    if not of_path(rec, "serving") or not rec.get("trace"):
        return None
    flops = work.model_flops(rec["model"], [], decode_contexts(rec))
    return mfu(rec, flops, decode_phase_s(rec))
