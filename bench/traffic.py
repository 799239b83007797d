"""One general generator for every traffic mix (``bench/traffic/*.json``).

A mix is data: loop kind (``open`` or ``closed``), arrival process and
rate, length distributions with their clips, the sampling mix and the
pre-window ramp.  The seed never changes the work, only its order:
requests come in cycles of ``cycle`` requests, and each cycle holds the
same multiset of prompt lengths, output lengths, inter-arrival gaps and
sampling policies (fixed quantiles of the stated distributions), which
the seed permutes.  Token ids are drawn from the seed.  Request ``rid``
is a pure function of ``(seed, rid)`` and the mix: growing a trace
extends it, never reshuffles it (prefix-stable).

A closed loop with ``"start": "steady"`` begins from the set of
sessions a long-running server holds: its first ``slots`` requests are
sessions caught mid-output.  Their output lengths are length-biased
quantiles of the output distribution (a long session is in flight
longer, so more often), each is a fixed share of the way through
(shares (i + 0.5) / slots), and the tokens it has already produced are
part of its prompt (``history``), to be prefilled before the window.
The set is the same for every seed; the seed permutes it.
"""
from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass
class Spec:
    """One request as the generator makes it.  ``t_due`` is seconds
    after the start of arrivals; ``temperature == 0`` is greedy."""

    rid: int
    prompt: list
    max_new: int
    t_due: float
    temperature: float
    top_p: float
    history: int = 0          # prompt tokens that stand for output

    @property
    def greedy(self) -> bool:
        return self.temperature == 0.0


def load(path) -> dict:
    with open(path) as f:
        mix = json.load(f)
    if mix["loop"] not in ("open", "closed"):
        raise ValueError(f"{path}: loop must be open or closed")
    return mix


def _quantiles(dist: dict, n: int) -> np.ndarray:
    """``n`` lengths at the fixed probabilities (i + 0.5) / n of a
    clipped distribution (``lognormal`` by median and sigma, or
    ``uniform`` over [min, max])."""
    p = (np.arange(n) + 0.5) / n
    if dist["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(x)) for x in p])
        v = dist["median"] * np.exp(dist["sigma"] * z)
    elif dist["dist"] == "uniform":
        v = dist["min"] + p * (dist["max"] - dist["min"])
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(np.rint(v), dist["min"], dist["max"]).astype(np.int64)


def _gaps(n: int, total: float) -> np.ndarray:
    """``n`` exponential inter-arrival gaps at the fixed probabilities
    (i + 0.5) / n, scaled to sum to ``total``."""
    p = (np.arange(n) + 0.5) / n
    g = -np.log1p(-p)
    return g * (total / g.sum())


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence(
        [int(seed) & (2**64 - 1), int(seed) >> 64, *stream]))


_CYCLE_STREAM, _TOKEN_STREAM, _PAIR_STREAM, _AGE_STREAM = 1, 2, 6, 7


def _length_biased(dist: dict, n: int, fine: int = 1024) -> np.ndarray:
    """``n`` lengths at the fixed probabilities (i + 0.5) / n of the
    length-biased form of a clipped distribution: the lengths of the
    sessions found in flight at a random instant of a closed loop."""
    q = np.sort(_quantiles(dist, fine))
    cdf = np.cumsum(q) / q.sum()
    return q[np.searchsorted(cdf, (np.arange(n) + 0.5) / n)]


class Traffic:
    """The request stream of one mix under one seed.

    ``request(k)`` is the k-th request of the stream.  For an open
    loop ``t_due`` accumulates the cycle's permuted exponential gaps;
    for a closed loop it is 0: the driver submits the first ``slots``
    (``max_batch``) requests at the start and the next one whenever
    one finishes."""

    def __init__(self, mix: dict, seed: int, vocab: int, slots: int = 0):
        self.mix, self.seed, self.vocab = mix, int(seed), int(vocab)
        self.n = int(mix.get("cycle", 64))
        self.prompt_q = _quantiles(mix["prompt"], self.n)
        # outputs are paired with prompts by one fixed shuffle, the same
        # for every seed, so a cycle's (prompt, output) pairs are too
        self.output_q = _quantiles(mix["output"], self.n)[
            _rng(0, _PAIR_STREAM).permutation(self.n)]
        shares = [s["share"] for s in mix["sampling"]]
        if abs(sum(shares) - 1.0) > 1e-9:
            raise ValueError("sampling shares must sum to 1")
        counts = np.floor(np.array(shares) * self.n + 1e-9).astype(int)
        counts[0] += self.n - counts.sum()
        self.policy_q = np.repeat(np.arange(len(shares)), counts)
        # an open loop's pre-window ramp is a cycle of its own, of
        # ramp_s * rate requests whose gaps sum to ramp_s, so the window
        # starts with cycle 0 and holds whole cycles when it lasts a
        # multiple of cycle / rate
        self.n_ramp = 0
        if mix["loop"] == "open":
            rate = float(mix["arrivals"]["rate_per_s"])
            self.gap_q = _gaps(self.n, self.n / rate)
            self.n_ramp = int(round(float(mix.get("ramp_s", 0.0)) * rate))
            if self.n_ramp:
                self.ramp = (_quantiles(mix["prompt"], self.n_ramp),
                             _quantiles(mix["output"], self.n_ramp)[
                                 _rng(0, _PAIR_STREAM).permutation(
                                     self.n_ramp)],
                             _gaps(self.n_ramp, float(mix["ramp_s"])))
        self.steady = mix.get("start") == "steady"
        if self.steady:
            if mix["loop"] != "closed" or slots < 1:
                raise ValueError("a steady start needs a closed loop and "
                                 "its number of slots")
            self.n_ramp = int(slots)
            fixed = _rng(0, _AGE_STREAM)
            olen = _length_biased(mix["output"], self.n_ramp)
            share = ((np.arange(self.n_ramp) + 0.5)
                     / self.n_ramp)[fixed.permutation(self.n_ramp)]
            age = np.floor(share * olen).astype(np.int64)
            self.ramp = (_quantiles(mix["prompt"], self.n_ramp)[
                             fixed.permutation(self.n_ramp)],
                         olen - age, np.zeros(self.n_ramp), age)
        self._cycles: dict = {}
        self._due: list = [0.0]

    def _cycle(self, c: int):
        """Cycle ``c``'s (prompts, outputs, policies, gaps, histories);
        cycle -1 is an open loop's ramp or a closed loop's steady set."""
        if c not in self._cycles and c < 0:
            rng = _rng(self.seed, _CYCLE_STREAM, 2**32 - 1)
            perm = rng.permutation(self.n_ramp)
            pols = np.resize(self.policy_q, self.n_ramp)[
                rng.permutation(self.n_ramp)]
            plen, olen, gaps = self.ramp[:3]
            hist = (self.ramp[3][perm] if self.steady
                    else np.zeros(self.n_ramp, np.int64))
            self._cycles[c] = (plen[perm], olen[perm], pols,
                               gaps[rng.permutation(self.n_ramp)], hist)
        if c not in self._cycles:
            rng = _rng(self.seed, _CYCLE_STREAM, c)
            perm = [rng.permutation(self.n) for _ in range(3)]
            gaps = (self.gap_q[perm[2]] if self.mix["loop"] == "open"
                    else np.zeros(self.n))
            self._cycles[c] = (self.prompt_q[perm[0]],
                               self.output_q[perm[0]],
                               self.policy_q[perm[1]], gaps,
                               np.zeros(self.n, np.int64))
        return self._cycles[c]

    def _tokens(self, rid: int, n: int):
        return _rng(self.seed, _TOKEN_STREAM, rid).integers(
            0, self.vocab, n).tolist()

    def _policy(self, k: int) -> tuple:
        s = self.mix["sampling"][int(k)]
        return float(s.get("temperature", 0.0)), float(s.get("top_p", 1.0))

    def _where(self, k: int) -> tuple:
        """(cycle, index in it) of the ``k``-th request of the stream."""
        if k < self.n_ramp:
            return -1, k
        return divmod(k - self.n_ramp, self.n)

    def due(self, k: int) -> float:
        """When the ``k``-th request of an open loop falls due, in
        seconds after the start of arrivals."""
        while len(self._due) <= k:
            c, j = self._where(len(self._due) - 1)
            self._due.append(self._due[-1] + float(self._cycle(c)[3][j]))
        return self._due[k]

    def request(self, k: int) -> Spec:
        """The ``k``-th request of the stream (its id is ``k``)."""
        c, j = self._where(k)
        plen, olen, pol, _, hist = self._cycle(c)
        temp, top_p = self._policy(pol[j])
        return Spec(k, self._tokens(k, int(plen[j] + hist[j])),
                    int(olen[j]),
                    self.due(k) if self.mix["loop"] == "open" else 0.0,
                    temp, top_p, int(hist[j]))


def mix_path(root: Path, name: str) -> Path:
    return root / "bench" / "traffic" / f"{name}.json"
