"""Model step, whole decode program: FLOPs the window's decode calls need
(each tenant's model family's ``decode_step_flops``: matmuls and head for
live rows, attention over live context) over their device time times the
chip's bf16 peak, in percent."""

from tracered import select

DECODE_PROGRAM = "jit_step"


def read(r):
    if r.trace is None or not r.rec.decode_calls:
        return None
    busy = sum(select(r.trace["modules"], DECODE_PROGRAM))
    if busy <= 0:
        return None
    work = sum(r.families[c["tenant"]].decode_step_flops(
        r.archs[c["tenant"]], c["ctx"]) for c in r.rec.decode_calls)
    return 100.0 * work / (busy * r.peak["bf16_flops"])
