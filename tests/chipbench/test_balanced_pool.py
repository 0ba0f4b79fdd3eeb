"""The closed loop's pool in the balanced order
(``chipbench/traffic/balanced.py``): the generator's lengths, the seed's
order and pairing, and the same work in every stretch whatever the seed."""

import json
import os
import statistics
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench.traffic import balanced, generator  # noqa: E402

MIX = {"pool_requests": 256,
       "prompt_tokens": {"dist": "lognormal", "median": 4096, "sigma": 1.0,
                         "min": 512, "max": 24576},
       "output_tokens": {"dist": "uniform", "min": 256, "max": 1024}}
SEEDS = [7, 2**31 + 12345, 4100000021, 2160000019]


def lengths(pool):
    return (np.array([len(r.prompt) for r in pool]),
            np.array([r.max_new_tokens for r in pool]))


def spread(values):
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


@pytest.mark.parametrize("seed", SEEDS)
def test_the_lengths_are_the_generators_and_the_order_is_the_seeds(seed):
    p, o = lengths(balanced.closed_pool(MIX, seed, vocab=1000))
    q, r = lengths(generator.closed_pool(MIX, seed, vocab=1000))
    assert sorted(p) == sorted(q) and sorted(o) == sorted(r)
    again = balanced.closed_pool(MIX, seed, vocab=1000)
    assert (lengths(again)[0] == p).all() and (lengths(again)[1] == o).all()
    assert all(0 <= x.prompt.min() and x.prompt.max() < 1000 for x in again)
    other = lengths(balanced.closed_pool(MIX, seed + 1, vocab=1000))
    assert (other[0] != p).any() and (other[1] != o).any()
    # the pairing is the seed's too, not only the order of fixed pairs
    assert set(zip(p, o)) != set(zip(*other))


@pytest.mark.parametrize("bits", [1, 3, 5, 8])
def test_every_aligned_run_is_a_stratified_sample_in_both_lengths(bits):
    n = 256
    x, y, m = balanced.sobol_points(n)
    rng = generator.rng_for(11, "requests")
    x, y = balanced.scramble(x, m, rng), balanced.scramble(y, m, rng)
    assert sorted(x) == sorted(y) == list(range(n))
    run = 1 << bits
    for start in range(0, n, run):
        a, b = x[start:start + run], y[start:start + run]
        # one point in each of the run's equal intervals of either length
        assert sorted(a >> (m - bits)) == list(range(run))
        assert sorted(b >> (m - bits)) == list(range(run))
        # and one in each box of every dyadic grid with as many boxes
        for i in range(bits + 1):
            boxes = set(zip(a >> (m - i), b >> (m - (bits - i))))
            assert len(boxes) == run


def test_a_window_holds_the_same_work_whatever_the_seed():
    # the prompt tokens of the 110 requests a window takes, from wherever in
    # the pool the ramp left off: under a permutation of the whole pool they
    # spread by more than a tenth, here by a few parts in a hundred
    def window_tokens(make, seed, first):
        p, _ = lengths(make(MIX, seed, vocab=10))
        return int(np.take(p, np.arange(first, first + 110), mode="wrap").sum())
    seeds = [1000003 * k + 17 for k in range(24)]
    for first in (32, 41, 57):
        even = [window_tokens(balanced.closed_pool, s, first) for s in seeds]
        drawn = [window_tokens(generator.closed_pool, s, first) for s in seeds]
        assert spread(even) < 0.03 < 0.08 < spread(drawn)
    # the long prompts are not all paired with long outputs, nor with short
    for s in seeds[:6]:
        p, o = lengths(balanced.closed_pool(MIX, s, vocab=10))
        assert abs(np.corrcoef(np.argsort(np.argsort(p)), o)[0, 1]) < 0.1


def test_a_pool_that_is_no_power_of_two_and_the_cells_own_mix():
    small = dict(MIX, pool_requests=12)
    p, o = lengths(balanced.closed_pool(small, 5, vocab=10))
    q, r = lengths(generator.closed_pool(small, 5, vocab=10))
    assert sorted(p) == sorted(q) and sorted(o) == sorted(r)
    with open(os.path.join(ROOT, "chipbench", "traffic",
                           "agent-longmix-closed.json")) as f:
        mix = json.load(f)
    pool = balanced.closed_pool(mix, 2160000019, vocab=10)
    assert len(pool) == mix["pool_requests"] == 256
    assert max(len(x.prompt) + x.max_new_tokens for x in pool) <= 24576 + 1024
