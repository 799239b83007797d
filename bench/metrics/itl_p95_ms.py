"""95th percentile of the gaps between two delivered tokens of one
request, over every gap that closes in the window: the stalls that
prefill puts into decoding streams.  At ~330 gaps a window some 16
lie beyond it."""
from bench.metrics._util import pct


def read(run, name):
    v = pct(run.gaps, 95)
    return None if v is None else v * 1e3
