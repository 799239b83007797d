"""Device: the share of the traced window in which no operation ran on
the device, averaged over the chips, in percent."""
from bench import trace_reduce


def read(run, name):
    if run.trace is None or not run.trace["chips"]:
        return None
    return 100.0 * (1.0 - trace_reduce.busy_share(run.trace))
