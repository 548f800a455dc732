"""Share of the traced window in which no program ran on the device."""
from perfbench.readers import device_idle


def read(rec):
    return device_idle(rec)
