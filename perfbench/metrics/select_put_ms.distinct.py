"""``select.put`` program span summed per engine step: handing each padded
chunk and the weights to the device (``jnp.asarray``), which returns once
the host-to-device copy is under way or done."""
from perfbench.readers import span_ms_per_step


def read(rec):
    return span_ms_per_step(rec, "select.put")
