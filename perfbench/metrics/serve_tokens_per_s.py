"""Output tokens that reached the host in the window, useful ones only (a
request's own tokens, not the padded decode rows of requests already done),
over the window's seconds (whole batches back to back)."""
from perfbench.readers import of_path


def read(rec):
    if not of_path(rec, "serving") or rec["window_s"] <= 0:
        return None
    return rec["tokens_useful"] / rec["window_s"]
