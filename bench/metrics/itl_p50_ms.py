"""Median gap between two delivered tokens of one request, over every
gap that closes in the window."""
from bench.metrics._util import pct


def read(run, name):
    v = pct(run.gaps, 50)
    return None if v is None else v * 1e3
