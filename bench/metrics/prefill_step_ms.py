"""Model step: device time per execution of the prefill-window program,
in the traced window."""
from bench.metrics._util import PREFILL_MODULE, step_ms


def read(run, name):
    return step_ms(run, PREFILL_MODULE)
