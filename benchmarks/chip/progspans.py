"""The program's own host-clock spans (``repro.serving.tracing``) inside a
run's window, for the per-layer readers.

Spans are timed with ``time.perf_counter_ns()``, the clock of the window's
``t_open`` and ``t_close``. A program that records no spans, or whose ring
dropped spans from inside the window, gives None.
"""
from __future__ import annotations


def bounds(r):
    """The window as ``(lo_ns, hi_ns)``."""
    return int(r.e2e["t_open"] * 1e9), int(r.e2e["t_close"] * 1e9)


def recorder():
    try:
        from repro.serving import tracing
    except ImportError:             # a program without spans
        return None
    return tracing.RECORDER


def window(r):
    """Spans that start and end inside the window, or None."""
    rec = recorder()
    return rec.window(*bounds(r)) if rec is not None else None
