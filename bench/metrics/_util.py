"""What several readers share: percentiles, the work of the substrate
calls in the traced window, and the device ops of the paged kernels."""
from __future__ import annotations

import numpy as np

from bench import costs, trace_reduce

# device op names of the Pallas kernels (``kernels/paged_attention.py``):
# the custom calls are named after the ``ops`` entry points
PREFILL_KERNEL = r"^%paged_prefill_attention\."
DECODE_KERNEL = r"^%paged_attention\."
# module names of the engine's jitted steps
PREFILL_MODULE = "jit_prefill"
DECODE_MODULE = "jit_step"


def pct(values, q: float):
    v = np.asarray(values, float)
    return float(np.percentile(v, q)) if v.size else None


def step_ms(run, module: str):
    if run.trace is None:
        return None
    t = trace_reduce.module_times(run.trace, module)
    return float(np.mean(t)) / 1e6 if t else None


def heads(run):
    m, tp = run.model, run.tp
    return m["h"] // tp, max(m["hkv"] // tp, 1), m["dh"]


def kernel_roofline(run, kind: str, pattern: str):
    """Roofline time of the attention work the traced ``kind`` calls
    needed, over the device time of the kernel's ops, in percent."""
    if run.trace is None or run.peak is None:
        return None
    dev = trace_reduce.op_time(run.trace, pattern) / 1e9
    if dev <= 0:
        return None
    h, hkv, dh = heads(run)
    best = 0.0
    for c in run.traced_calls():
        if c[0] != kind:
            continue
        fl, nb = (costs.prefill_attention(c[2], c[3], h, hkv, dh)
                  if kind == "prefill" else
                  costs.decode_attention(c[2], h, hkv, dh))
        best += costs.roofline_s(fl, nb, run.peak) * run.model["layers"]
    return 100.0 * best / dev if best > 0 else None
