"""Scheduler: time from a request's due time to the start of the tick
that admitted it, 90th percentile over the requests due in the window
(not admitted by its end: the time waited so far)."""
from bench.metrics._util import pct


def read(run, name):
    w = [min(r.admitted if r.admitted is not None else run.W1, run.W1)
         - r.due for r in run.due_in_window()]
    v = pct(w, 90)
    return None if v is None else v * 1e3
