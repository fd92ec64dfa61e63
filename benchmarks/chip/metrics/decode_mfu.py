"""Model step, whole decode program: FLOPs the window's decode calls need
(matmuls and head for live rows, attention over live context; flops.py)
over their device time times the chip's bf16 peak, in percent."""

from flops import decode_step_flops
from tracered import select

DECODE_PROGRAM = "jit_step"


def read(r):
    if r.trace is None or not r.rec.decode_calls:
        return None
    busy = sum(select(r.trace["modules"], DECODE_PROGRAM))
    if busy <= 0:
        return None
    work = sum(decode_step_flops(r.archs[c["tenant"]], c["ctx"])
               for c in r.rec.decode_calls)
    return 100.0 * work / (busy * r.peak["bf16_flops"])
