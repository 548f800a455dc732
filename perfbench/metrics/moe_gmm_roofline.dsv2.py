"""``moe_gmm``'s share of its roofline: least time for the rows routed to
the experts held here (6 D F FLOPs each, from the engine's counter), the
held experts' weights each model step touches and the rows in and out,
over the kernel's device time in the traced batches."""
from perfbench.readers_mla_moe import moe_gmm_roofline


def read(rec):
    return moe_gmm_roofline(rec)
