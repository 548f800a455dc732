"""``flash_attention``'s share of its roofline: least time for causal
attention over each request's own prompt (Q, K, V read and O written once)
over the kernel's device time in the traced batches."""
from perfbench import work
from perfbench.readers import kernel_roofline, of_path, prompt_lens


def read(rec):
    if not of_path(rec, "serving"):
        return None
    flops, nbytes = work.flash_attention_work(rec["model"], prompt_lens(rec))
    return kernel_roofline(rec, "flash_attention", flops, nbytes)
