"""Device time per execution of the jitted decode step (the step that runs
``mla_decode_attention``)."""
from perfbench.readers_mla_moe import decode_step_ms


def read(rec):
    return decode_step_ms(rec)
