"""Median device idle time between consecutive decode steps: the host's
per-token sync, sampling and billing."""
from perfbench.harness import median
from perfbench.readers import module_gaps_s, of_path


def read(rec):
    if not of_path(rec, "serving"):
        return None
    gaps = module_gaps_s(rec, "decode_attention")
    return 1e3 * median(gaps) if gaps else None
