"""Tasks placed, executed and billed in the window, over the window's
seconds (closed loop: whole engine steps back to back)."""
from perfbench.readers import of_path


def read(rec):
    if not of_path(rec, "scheduler") or rec["window_s"] <= 0:
        return None
    return rec["tasks_done"] / rec["window_s"]
