"""Device time per execution of the jitted prefill (the step that runs
``flash_attention``)."""
from perfbench.readers import module_ms, of_path


def read(rec):
    return module_ms(rec, "flash_attention") if of_path(rec, "serving") \
        else None
