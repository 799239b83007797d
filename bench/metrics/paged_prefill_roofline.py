"""Kernels: the paged prefill-window kernel's roofline time for the
attention the traced prefill calls needed, over its device time, in
percent."""
from bench.metrics._util import PREFILL_KERNEL, kernel_roofline


def read(run, name):
    return kernel_roofline(run, "prefill", PREFILL_KERNEL)
