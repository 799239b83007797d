"""The whole step against the chip's peak: model FLOPs of the tokens
the prefill and decode calls processed in the traced window (real
lengths only; padding rows and empty slots are no work), over the
traced window times the chips times the peak bf16 FLOP/s, in
percent.  The configuration's family counts the FLOPs."""
import numpy as np


def read(run, name):
    if run.trace is None or run.peak is None:
        return None
    tokens = attended = rows = 0
    for c in run.traced_calls():
        if c[0] == "prefill":
            s, n = c[2].astype(np.int64), c[3].astype(np.int64)
            tokens += int(n.sum())
            attended += int((n * s + n * (n + 1) // 2).sum())
        else:
            lens = c[2].astype(np.int64)
            tokens += int((lens > 0).sum())
            attended += int(lens.sum())
            rows += int((lens > 0).sum())
    a, b = run.trace_t
    rows += sum(r.first is not None and a <= r.first < b
                for r in run.recs.values())
    if tokens == 0:
        return None
    fl = run.family.model_flops(run.model, tokens, attended, rows)
    window = run.trace["window_ns"] / 1e9
    return 100.0 * fl / (window * run.chips * run.peak["bf16_flops_per_s"])
