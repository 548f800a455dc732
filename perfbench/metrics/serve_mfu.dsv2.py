"""This chip's share of its bf16 peak over the traced window: model FLOPs
of the traced batches (expanded attention in prefill, absorbed in decode,
the dense layer, shared experts, router, the rows routed to held experts
from the engine's counter, and the unembedding) over the window times the
peak."""
from perfbench.readers_mla_moe import serve_mfu


def read(rec):
    return serve_mfu(rec)
