"""95th percentile over all consecutive-token gaps of all requests in the
window, on the host clock."""
from perfbench.harness import percentile
from perfbench.readers import of_path


def read(rec):
    if not of_path(rec, "serving") or not rec["token_gaps_s"]:
        return None
    return 1e3 * percentile(rec["token_gaps_s"], 95)
