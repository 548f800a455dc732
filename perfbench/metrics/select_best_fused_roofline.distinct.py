"""``select_best_fused``'s share of its roofline: the least time the chip
needs for the traced steps' scoring work (every fresh (task, node) cell of
Eq. 3, the node columns once per step, each task's profile and winner) over
the kernel's device time. Every task of this cell has its own profile, so
every row is scored fresh."""
from perfbench import work
from perfbench.readers import kernel_roofline, of_path


def read(rec):
    if not of_path(rec, "scheduler") or not rec.get("steps"):
        return None
    flops, nbytes = work.select_work(rec["tasks_done"], rec["nodes"],
                                     len(rec["steps"]))
    return kernel_roofline(rec, "select_best_fused", flops, nbytes)
