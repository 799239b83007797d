"""A whole run on the CPU (the chip check skipped) at toy size: sound,
it is correct; with the timed path broken underneath, ``correct``
comes out false, once for each fault a served cell can have."""
import time

import numpy as np
import pytest

from bench_tiny import make_root

from bench import harness

VOCAB = 128


class Fault:
    """The substrate with one fault planted in the decode step."""

    def __init__(self, inner, kind):
        self.inner, self.kind = inner, kind

    def prefill(self, *a):
        return self.inner.prefill(*a)

    def decode(self, pool, tokens, pos, bt, lens, samp):
        toks, new_pool = self.inner.decode(pool, tokens, pos, bt, lens, samp)
        if self.kind == "token":
            # a token altered where it is produced
            t = np.array(toks)
            t[lens > 0] = (t[lens > 0] + 1) % VOCAB
            return t, new_pool
        if self.kind == "half":
            # half of the batch's live rows left out: they echo their
            # input token
            t = np.array(toks)
            live = np.flatnonzero(lens > 0)
            out = live[len(live) // 2:]
            t[out] = np.asarray(tokens)[out]
            return t, new_pool
        if self.kind == "state":
            # the step returns its state (the KV pool) unchanged
            return toks, pool
        return toks, new_pool

    def __getattr__(self, name):
        return getattr(self.inner, name)


@pytest.mark.parametrize("kind,expect", [("none", True), ("token", False),
                                         ("half", False), ("state", False)])
def test_fault_makes_correct_false(tmp_path, kind, expect):
    root = make_root(tmp_path)
    res = harness.run(root, "tiny16.open", 2**34 + 3, 1.5, False,
                      time.perf_counter(), require_chip=False,
                      wrap=lambda inner: Fault(inner, kind))
    assert res["correct"] is expect, res["checks"]
    assert list(res)[-1] == "checks"
    assert res["checks"]["tokens_checked"]["value"] >= \
        res["checks"]["tokens_checked"]["limit"]
