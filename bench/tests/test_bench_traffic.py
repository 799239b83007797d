"""The traffic generator: seeded, prefix-stable, clipped, and the same
work for every seed."""
import json

import numpy as np
import pytest

from bench_tiny import REPO

from bench import traffic

MIXES = {n: json.loads((REPO / "bench" / "traffic" / f"{n}.json").read_text())
         for n in ("code", "longout")}
BIG_SEED = 2**33 + 12345
SLOTS = 28


def _trace(mix, seed, n=80):
    tr = traffic.Traffic(mix, seed, 151936, SLOTS)
    return [tr.request(k) for k in range(n)]


@pytest.mark.parametrize("name", sorted(MIXES))
def test_same_seed_same_trace_other_seed_other_trace(name):
    a, b = _trace(MIXES[name], BIG_SEED), _trace(MIXES[name], BIG_SEED)
    c = _trace(MIXES[name], BIG_SEED + 1)
    key = lambda t: [(r.prompt, r.max_new, r.t_due, r.temperature)  # noqa
                     for r in t]
    assert key(a) == key(b)
    assert key(a) != key(c)


@pytest.mark.parametrize("name", sorted(MIXES))
def test_prefix_stable_per_rid(name):
    long = _trace(MIXES[name], 7, 200)
    tr = traffic.Traffic(MIXES[name], 7, 151936, SLOTS)
    for k in (150, 3, 77, 0):        # any order, any length
        r = tr.request(k)
        assert (r.prompt, r.max_new, r.t_due) == \
            (long[k].prompt, long[k].max_new, long[k].t_due)


@pytest.mark.parametrize("name", sorted(MIXES))
def test_clips_hold(name):
    mix = MIXES[name]
    for r in _trace(mix, 3, 256):
        # a steady session's history is output it has already produced
        p, o = len(r.prompt) - r.history, r.max_new + r.history
        assert mix["prompt"]["min"] <= p <= mix["prompt"]["max"]
        assert mix["output"]["min"] <= o <= mix["output"]["max"]
        assert all(0 <= t < 151936 for t in r.prompt)


@pytest.mark.parametrize("name", sorted(MIXES))
def test_every_seed_gets_the_same_work(name):
    mix = MIXES[name]
    n, r0 = mix["cycle"], traffic.Traffic(mix, 1, 9, SLOTS).n_ramp
    sizes = [sorted((len(r.prompt), r.max_new)
                    for r in _trace(mix, s, r0 + n)[r0:])
             for s in (1, 2, BIG_SEED)]
    assert sizes[0] == sizes[1] == sizes[2]
    greedy = [sum(r.greedy for r in _trace(mix, s, r0 + n)[r0:])
              for s in (1, 2)]
    assert greedy == [n // 2, n // 2]


def test_open_loop_arrivals_keep_the_rate():
    mix = MIXES["code"]
    tr = traffic.Traffic(mix, 11, 151936)
    n, r0 = 4 * mix["cycle"], tr.n_ramp
    due = [tr.due(k) for k in range(r0 + n + 1)]
    assert all(b >= a for a, b in zip(due, due[1:]))
    rate = mix["arrivals"]["rate_per_s"]
    assert due[r0 + n] - due[r0] == pytest.approx(n / rate, rel=1e-9)


def test_open_loop_ramp_is_a_cycle_of_its_own():
    # the window (starting at ramp_s) holds whole cycles, the same
    # multiset of work for every seed
    mix = MIXES["code"]
    rate, ramp = mix["arrivals"]["rate_per_s"], mix["ramp_s"]
    n = mix["cycle"]
    for seed in (1, BIG_SEED):
        tr = traffic.Traffic(mix, seed, 151936)
        assert tr.n_ramp == round(ramp * rate)
        assert tr.due(tr.n_ramp) == pytest.approx(ramp, rel=1e-9)
        assert tr.due(tr.n_ramp + n) == pytest.approx(ramp + n / rate,
                                                      rel=1e-9)
    window = [sorted((len(r.prompt), r.max_new) for r in
                     _trace(mix, s, n + round(ramp * rate))[-n:])
              for s in (1, BIG_SEED)]
    assert window[0] == window[1]


def test_steady_start_is_the_same_set_mid_output_for_every_seed():
    mix = MIXES["longout"]
    sets = [_trace(mix, s, SLOTS) for s in (1, BIG_SEED)]
    key = lambda t: sorted((len(r.prompt), r.history, r.max_new)  # noqa
                           for r in t)
    assert key(sets[0]) == key(sets[1])
    assert [r.rid for r in sets[0]] == list(range(SLOTS))
    for r in sets[0]:
        assert r.history >= 0 and r.max_new >= 1
    # caught mid-output: about half-way through, and length-biased, so
    # longer than a session drawn from the stream
    steady = [r.history + r.max_new for r in sets[0]]
    stream = [r.max_new for r in _trace(mix, 1, SLOTS + 256)[SLOTS:]]
    assert np.mean(steady) > 1.2 * np.mean(stream)
    share = sum(r.history for r in sets[0]) / sum(steady)
    assert 0.35 < share < 0.65
    with pytest.raises(ValueError):
        traffic.Traffic(mix, 1, 9)           # a steady start needs slots
