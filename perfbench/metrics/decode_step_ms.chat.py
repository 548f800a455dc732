"""Device time per execution of the jitted decode step (the step that runs
``decode_attention``)."""
from perfbench.readers import module_ms, of_path


def read(rec):
    return module_ms(rec, "decode_attention") if of_path(rec, "serving") \
        else None
