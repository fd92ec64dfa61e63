"""Kernel (kernels/paged_attention, decode): the least time the chip
could take for the bytes and FLOPs the algorithm needs (live pages of K and
V of the batch, q and the output; flops.py) over the kernel's device time
in the trace, in percent. Memory bandwidth bounds it at these shapes.

Dense (grouped-query attention) families only: it counts K and V bytes from
``head_dim`` and ``num_key_value_heads``. A cell of another attention lists
a roofline reader of its own, not this one."""

from flops import paged_decode_bytes, paged_decode_flops, roofline_seconds
from tracered import select

KERNEL = "paged_decode"


def read(r):
    if r.trace is None or not r.rec.decode_calls:
        return None
    spent = sum(select(r.trace["op_events"], KERNEL))
    if spent <= 0:
        return None
    least = 0.0
    for c in r.rec.decode_calls:
        arch = r.archs[c["tenant"]]
        t, _bound = roofline_seconds(
            paged_decode_flops(arch, c["ctx"]),
            paged_decode_bytes(arch, c["ctx"], r.page_size), r.peak)
        least += t * arch["num_hidden_layers"]
    return 100.0 * least / spent
