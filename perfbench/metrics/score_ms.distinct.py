"""``score`` program span summed per engine step: padding to the shape
bucket in float32, the host-to-device copy, the ``select_best_fused``
kernel and the winners' read-back."""
from perfbench.readers import span_ms_per_step


def read(rec):
    return span_ms_per_step(rec, "score")
