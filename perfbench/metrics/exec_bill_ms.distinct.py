"""Engine step wall time minus its ``select`` span, per step: executing
and billing the placed batch (``execute_batch``, ``CarbonMonitor``)."""
from perfbench.readers import exec_bill_ms


def read(rec):
    return exec_bill_ms(rec)
