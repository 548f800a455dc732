"""``featurize`` program span (``VectorizedPolicy``: the (rows, N, 8)
feature tensor from the cluster's FeatureCache) summed per engine step."""
from perfbench.readers import span_ms_per_step


def read(rec):
    return span_ms_per_step(rec, "featurize")
