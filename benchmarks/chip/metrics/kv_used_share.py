"""KV allocator (core/kv_allocator.py): mean over the window's engine steps
of used pages over total pages, read after each step, in percent."""


def read(r):
    kv = r.rec.kv_used
    return 100.0 * sum(kv) / len(kv) if kv else None
