"""Admission and scheduler (serving/engine.py ``_admit``, serving/scheduler.py):
95th percentile of the wait from a request's due time to the start of the
engine step that gave it a batch slot, over the requests due in the window;
one still waiting at the window's close counts at its age then. Host clock."""


def read(r):
    lo, hi = r.e2e["t_open"], r.e2e["t_close"]
    waits = [(tr.admitted if tr.admitted is not None and tr.admitted <= hi
              else hi) - tr.due
             for tr in r.rec.tracks.values() if lo <= tr.due < hi]
    if not waits:
        return None
    from harness import p95
    return p95(waits)
