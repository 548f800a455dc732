"""``mla_decode_attention``'s share of its roofline: least time for each
useful decode row's absorbed attention over the latent cache up to its own
position (the latents read once for all heads, plus the queries) over the
kernel's device time in the traced batches."""
from perfbench.readers_mla_moe import mla_decode_roofline


def read(rec):
    return mla_decode_roofline(rec)
