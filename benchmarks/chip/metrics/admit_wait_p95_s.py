"""Admission and scheduler (serving/engine.py ``submit`` to ``_admit``):
95th percentile of the program's ``request.queued`` span, from a request's
submission to its batch slot, over the requests submitted in the window;
one still waiting at the window's close counts at its age then. The
program's twin of queue_wait_p95_s, which times from the due time. Host
clock."""

from progspans import bounds, recorder


def read(r):
    rec = recorder()
    lo, hi = bounds(r)
    if rec is None or rec.window(lo, hi) is None:
        return None
    waits = [min(s.end_ns, hi) - s.start_ns for s in rec.spans
             if s.name == "request.queued" and lo <= s.start_ns < hi]
    waits += [hi - t for t in rec.opened("request.queued").values()
              if lo <= t < hi]
    if not waits:
        return None
    from harness import p95
    return 1e-9 * p95(waits)
