"""Transfer engine (core/transfer_engine.py): bytes the window's decode
calls copy from pinned host memory (m cycle layers, each one packed layer
buffer), over the output tokens of the window. A count from shapes, not a
measured copy."""


def read(r):
    if not r.rec.decode_calls or not r.e2e["out_tokens"]:
        return None
    return sum(c["host_bytes"] for c in r.rec.decode_calls) \
        / r.e2e["out_tokens"]
