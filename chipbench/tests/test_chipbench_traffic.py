"""The one traffic generator: equal seeds give equal inputs, different
seeds give different inputs over the same sizes. Both arrival kinds: the
benchmark's open-loop mix, and the same lengths as a closed loop of 16
clients (the kind a batch cell's data file would name)."""
import copy
import math

import numpy as np
import pytest

import chipbench_tiny  # noqa: F401
from harness import spec, traffic

MIXES = ("alpaca-poisson", "alpaca-poisson@closed16")


def _mix(name):
    base, _, closed = name.partition("@")
    mix = copy.deepcopy(spec.traffic(base))
    if closed:
        mix.update(arrivals={"kind": "closed", "clients": 16}, pool=96)
    return mix


def _median(dist):
    return dist["median"] if "median" in dist else \
        dist["mean"] / math.exp(dist["sigma"] ** 2 / 2)


@pytest.mark.parametrize("name", MIXES)
def test_equal_seeds_equal_inputs(name):
    mix = _mix(name)
    a = traffic.generate(mix, 32_064, 2**40 + 17, 30)
    b = traffic.generate(mix, 32_064, 2**40 + 17, 30)
    assert a.max_new == b.max_new and a.offsets == b.offsets
    assert all(np.array_equal(x, y) for x, y in zip(a.prompts, b.prompts))


@pytest.mark.parametrize("name", MIXES)
def test_different_seeds_different_order_same_sizes(name):
    mix = _mix(name)
    a = traffic.generate(mix, 32_064, 3, 30)
    b = traffic.generate(mix, 32_064, 2**40 + 3, 30)
    assert a.lengths() != b.lengths()
    assert not all(np.array_equal(x, y)
                   for x, y in zip(a.prompts, b.prompts))
    # the same multiset of sizes and gaps: every seed does the same work
    assert sorted(a.lengths()) == sorted(b.lengths())
    assert sorted(a.max_new) == sorted(b.max_new)
    assert np.allclose(sorted(np.diff([0.0] + a.offsets)),
                       sorted(np.diff([0.0] + b.offsets)))


@pytest.mark.parametrize("name", MIXES)
def test_sizes_follow_the_mix(name):
    mix = _mix(name)
    t = traffic.generate(mix, 64_000, 5, 30)
    lens = np.array(t.lengths())
    pl, ol = mix["prompt_len"], mix["output_len"]
    assert lens.min() >= pl["min"] and lens.max() <= pl["max"]
    assert min(t.max_new) >= ol["min"] and max(t.max_new) <= ol["max"]
    assert abs(np.median(lens) - _median(pl)) <= 0.05 * _median(pl) + 0.5
    assert all(p.dtype == np.int32 and p.max() < 64_000 for p in t.prompts)
    if mix["arrivals"]["kind"] == "poisson":
        rate = mix["arrivals"]["rate_per_s"]
        gaps = np.diff([0.0] + t.offsets)
        assert abs(gaps.mean() * rate - 1.0) < 0.05


def test_stratified_quantiles_are_sorted_and_clipped():
    q = traffic.quantiles({"dist": "lognormal", "median": 10, "sigma": 2,
                           "min": 5, "max": 20}, 50)
    assert list(q) == sorted(q) and q.min() == 5 and q.max() == 20


def test_a_published_mean_sets_the_median():
    """A mix may give the mean a trace study publishes; without clipping,
    the stratified lengths then average to it."""
    q = traffic.quantiles({"dist": "lognormal", "mean": 58.45,
                           "sigma": 0.8, "min": 1, "max": 10**6}, 4000)
    assert abs(q.mean() - 58.45) < 0.02 * 58.45


def test_balanced_blocks_hold_one_value_of_each_stratum():
    """With ``block``, every run of ``block`` consecutive requests takes
    one value from each stratum of the sorted set, so each stretch of a
    run carries the same load whatever the seed; the set is unchanged."""
    mix = spec.traffic("alpaca-poisson")
    k = mix["block"]
    for seed in (7, 2**40 + 7):
        t = traffic.generate(mix, 32_064, seed, 51)
        n = len(t.max_new)
        assert n % k == 0
        ref = traffic.quantiles(mix["output_len"], n)
        assert sorted(t.max_new) == sorted(ref.tolist())
        stratum = {}
        for r, v in enumerate(ref.tolist()):
            stratum.setdefault(v, set()).add(r // (n // k))
        gaps = np.diff([0.0] + t.offsets)
        for j in range(0, n, k):
            blk = t.max_new[j:j + k]
            # values shared by two strata may stand for either
            assert all(len(stratum[v]) > 1 or
                       sum(1 for w in blk if stratum[w] == stratum[v]) == 1
                       for v in blk)
            assert abs(gaps[j:j + k].sum() * mix["arrivals"]["rate_per_s"]
                       - k) < 0.5 * k
