"""Model step: device time per execution of the decode program, in the
traced window."""
from bench.metrics._util import DECODE_MODULE, step_ms


def read(run, name):
    return step_ms(run, DECODE_MODULE)
