"""Whole scheduling step's share of the chip's bf16 peak: the Eq. 3 work
of the traced steps over the traced window times the peak."""
from perfbench import work
from perfbench.readers import mfu, of_path


def read(rec):
    if not of_path(rec, "scheduler") or not rec.get("trace"):
        return None
    flops, _ = work.select_work(rec["tasks_done"], rec["nodes"],
                                len(rec["steps"]))
    return mfu(rec, flops, rec["trace"]["window_s"])
