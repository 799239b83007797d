"""Time to first token, 90th percentile, over every request due in the
window, timed from when it was due.  A request with no first token by
the window's end counts with the time it had waited so far."""
from bench.metrics._util import pct


def read(run, name):
    w = [min(r.first if r.first is not None else run.W1, run.W1) - r.due
         for r in run.due_in_window()]
    v = pct(w, 90)
    return None if v is None else v * 1e3
