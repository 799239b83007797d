"""Scheduler: time from a request's arrival to its first admission, as
the scheduler stamps it (``Request.t_admit``, on the engine's clock,
whose zero is the traffic's start), 90th percentile over the requests
due in the window.  A request not admitted by the window's end counts
with the time it had waited so far."""
from bench.metrics._util import pct


def read(run, name):
    reqs = [r.req for r in run.recs.values() if r.req is not None]
    if not reqs or not hasattr(reqs[0], "t_admit"):
        return None                 # a program that stamps no admission
    w = []
    for r in run.due_in_window():
        a = None if r.req is None else r.req.t_admit
        # the engine's clock reads due - t_arrive at its zero
        at = run.W1 if a is None else min(r.due - r.req.t_arrive + a, run.W1)
        w.append(at - r.due)
    v = pct(w, 90)
    return None if v is None else v * 1e3
