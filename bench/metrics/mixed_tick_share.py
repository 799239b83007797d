"""Scheduler: the share of decoding ticks that also carry prefill, in
percent, from the ``serve.plan`` counters of the ticks in the traced
window: ticks with ``decode_seqs > 0`` and ``prefill_tokens > 0`` over
those with ``decode_seqs > 0``.  Each such tick stretches the gap
between tokens of every decoding stream in it."""
from bench.metrics import _program


def read(run, name):
    plans = [s[3] for _, inner in _program.ticks(run) for s in inner
             if s[0] == "serve.plan"]
    decoding = [p for p in plans if p.get("decode_seqs", 0) > 0]
    if not decoding:
        return None
    mixed = sum(p.get("prefill_tokens", 0) > 0 for p in decoding)
    return 100.0 * mixed / len(decoding)
