"""``select.fetch`` program span summed per engine step: the host's wait
for each chunk's ``select_best_fused`` launch to finish and the read-back
of its winners (``np.asarray`` of the indices and scores)."""
from perfbench.readers import span_ms_per_step


def read(rec):
    return span_ms_per_step(rec, "select.fetch")
