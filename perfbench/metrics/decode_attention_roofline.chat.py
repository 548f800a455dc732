"""``decode_attention``'s share of its roofline: least time for each useful
decode row's attention over the cache positions up to its own (not the
whole cache) over the kernel's device time in the traced batches."""
from perfbench import work
from perfbench.readers import decode_contexts, kernel_roofline, of_path


def read(rec):
    if not of_path(rec, "serving"):
        return None
    flops, nbytes = work.decode_attention_work(rec["model"],
                                               decode_contexts(rec))
    return kernel_roofline(rec, "decode_attention", flops, nbytes)
