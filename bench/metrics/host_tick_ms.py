"""Scheduler: the host's own time per engine tick, median over the
ticks in the traced window: each ``serve.tick`` span less the
``serve.wait`` spans inside it, where the host waits for the device's
tokens.  The rest is scheduling, building inputs, dispatching and
retiring tokens, which the device waits for."""
import numpy as np

from bench.metrics import _program


def read(run, name):
    own = [(t[2] - t[1]) - sum(s[2] - s[1] for s in inner
                               if s[0] == "serve.wait")
           for t, inner in _program.ticks(run)]
    return float(np.median(own)) / 1e6 if own else None
