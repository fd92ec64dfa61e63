"""Model step (prefill): mean host time of one eager prefill chunk, the
self time of the program's ``engine.prefill_chunk`` span (serving/engine.py
``_prefill_step``: the chunk's dispatch), that is less the
``engine.prefill_wait`` inside it (on a prompt's last chunk, the host
blocked on the device for the first token), over the chunks of the window.
Host clock."""

from progspans import window


def read(r):
    spans = window(r)
    if spans is None:
        return None
    chunks = {s.sid: s.end_ns - s.start_ns for s in spans
              if s.name == "engine.prefill_chunk"}
    if not chunks:
        return None
    for s in spans:
        if s.name == "engine.prefill_wait" and s.parent in chunks:
            chunks[s.parent] -= s.end_ns - s.start_ns
    return 1e-6 * sum(chunks.values()) / len(chunks)
