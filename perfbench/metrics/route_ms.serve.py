"""``GreenRouter.route`` per batch, timed by the benchmark's router
subclass around the call (Eq. 3 over the pods: the fused select kernel at
its (8, 8) bucket and the read-back of the winner)."""
from perfbench.readers import of_path


def read(rec):
    if not of_path(rec, "serving") or not rec["route_s"]:
        return None
    return 1e3 * sum(rec["route_s"]) / len(rec["route_s"])
