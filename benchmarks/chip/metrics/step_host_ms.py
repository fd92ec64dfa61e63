"""Engine host loop (serving/engine.py ``ServingEngine.step``): mean over
the window's steps of the ``engine.step`` span less the time inside it
spent in ``engine.decode_wait`` (the host blocked on the decode program)
and ``engine.prefill_chunk`` (read by prefill_chunk_host_ms): the host's
own work between device programs. Host clock."""

from progspans import window

TAKEN_OUT = ("engine.decode_wait", "engine.prefill_chunk")


def read(r):
    spans = window(r)
    if spans is None:
        return None
    steps = {s.sid: s.end_ns - s.start_ns for s in spans
             if s.name == "engine.step"}
    if not steps:
        return None
    parent = {s.sid: s.parent for s in spans}
    for s in spans:
        if s.name in TAKEN_OUT:
            p = s.parent
            while p in parent and p not in steps:
                p = parent[p]
            if p in steps:
                steps[p] -= s.end_ns - s.start_ns
    return 1e-6 * sum(steps.values()) / len(steps)
