"""Kernels: the paged decode kernel's roofline time for the attention
the traced decode calls needed, over its device time, in percent."""
from bench.metrics._util import DECODE_KERNEL, kernel_roofline


def read(run, name):
    return kernel_roofline(run, "decode", DECODE_KERNEL)
