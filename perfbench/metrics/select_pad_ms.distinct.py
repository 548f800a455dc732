"""``select.pad`` program span summed per engine step: padding each
chunk of features to its power-of-two shape bucket in float32
(``VectorizedPolicy._pad_to_buckets``, a fresh zero array per chunk)."""
from perfbench.readers import span_ms_per_step


def read(rec):
    return span_ms_per_step(rec, "select.pad")
