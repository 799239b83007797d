"""The comparison that decides ``correct`` for a served model.

Once the window has closed and the program's state is freed, a sample
of the greedy requests that were served tokens (finished, or in flight
at the close: every token they hold came from the timed path), drawn
from the seed and always holding the one with the most served tokens,
is run through the plain reference of the configuration's family
(``bench/families/<family>.py``) over its prompt and its served
tokens.  For every served token the gap is how far its reference logit
lies below the reference's best logit at that position; the number
compared is the widest gap.  A greedy server that computes what the
configuration states picks the reference's best token up to rounding
near ties, so its widest gap stays small; a wrong token, a wrong cache
or a lower precision moves it far.

``control_gap`` reads the same sample with the control (the reference
in float8) put in the program's place: at each position the token the
control ranks first, and its gap under the float32 reference.
"""
from __future__ import annotations

import numpy as np

from . import families, traffic

_SAMPLE_STREAM = 4


def sample(done: list, n: int, seed: int) -> list:
    """Up to ``n`` served greedy requests, drawn from ``seed``, the one
    with the most served tokens always among them.  ``done`` holds
    ``(spec, served_tokens)`` pairs."""
    greedy = sorted((d for d in done if d[0].greedy and d[1]),
                    key=lambda d: d[0].rid)
    if not greedy:
        return []
    longest = max(greedy, key=lambda d: (len(d[1]), -d[0].rid))
    rest = [d for d in greedy if d is not longest]
    rng = traffic._rng(seed, _SAMPLE_STREAM)
    pick = rng.permutation(len(rest))[:max(n - 1, 0)]
    return [longest] + [rest[i] for i in sorted(pick)]


def _inputs(chosen):
    seqs, rows, toks = [], [], []
    for spec, out in chosen:
        p = len(spec.prompt)
        seqs.append(list(spec.prompt) + list(out[:-1]))
        rows.append(np.arange(p - 1, p - 1 + len(out)))
        toks.extend(out)
    return seqs, rows, np.asarray(toks, np.int64)


def gaps(root, conf: dict, seed: int, chosen: list, control: bool = False):
    """Per served token: the reference's best logit minus the logit of
    the token served (``control=False``) or of the token the float8
    control ranks first (``control=True``)."""
    ref = families.load(root, conf).Reference(conf, seed)
    seqs, rows, toks = _inputs(chosen)
    hs = ref.hidden(seqs, rows)
    if not control:
        best, _, got = ref.logits_stats(hs, toks)
        return best - got
    _, ctrl_tok, _ = ref.logits_stats(ref.hidden(seqs, rows, "fp8"),
                                      precision="fp8")
    best, _, got = ref.logits_stats(hs, ctrl_tok)
    return best - got


def verdict(root, conf: dict, seed: int, done: list, limits: dict,
            control: bool = False) -> tuple:
    """(correct, checks): the numbers compared, each with its limit.
    ``control=True`` judges the float8 control's tokens in place of
    the served ones."""
    chosen = sample(done, int(limits["sample_requests"]), seed)
    g = gaps(root, conf, seed, chosen, control) if chosen else np.zeros(0)
    n = int(g.size)
    widest = float(g.max()) if n else float("inf")
    checks = {
        "max_logit_gap": {"value": widest,
                          "limit": float(limits["max_logit_gap"])},
        "tokens_checked": {"value": n,
                           "limit": int(limits["min_tokens_checked"])},
    }
    ok = (n >= checks["tokens_checked"]["limit"]
          and widest <= checks["max_logit_gap"]["limit"])
    return ok, checks
