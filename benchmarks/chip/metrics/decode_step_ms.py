"""Model step (models/lm.py via Tenant.decode): mean device time of one
run of the jitted decode program, from the trace's XLA module events."""

from tracered import select

DECODE_PROGRAM = "jit_step"


def read(r):
    if r.trace is None:
        return None
    runs = select(r.trace["modules"], DECODE_PROGRAM)
    return 1e3 * sum(runs) / len(runs) if runs else None
