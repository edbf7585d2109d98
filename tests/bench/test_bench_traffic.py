"""The benchmark's traffic generator: determined by the seed, the same
work for every seed, lengths held to the mix's ranges."""
import collections
import json

import numpy as np
import pytest

from benchpath import BENCH
from harness import traffic


def _mix(name):
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


@pytest.mark.parametrize("name", ["chat-steady", "chat-burst"])
def test_same_seed_same_schedule(name):
    a = traffic.schedule(_mix(name), 2 ** 31 + 5, 10.0)
    b = traffic.schedule(_mix(name), 2 ** 31 + 5, 10.0)
    assert a == b
    pa = traffic.prompt_tokens(a, 2 ** 31 + 5, 50304)
    pb = traffic.prompt_tokens(b, 2 ** 31 + 5, 50304)
    assert all(np.array_equal(x, y) for x, y in zip(pa, pb))


@pytest.mark.parametrize("name", ["chat-steady", "chat-burst"])
def test_every_seed_offers_the_same_window(name):
    """Seeds permute a fixed multiset of gaps and lengths per segment: the
    window holds the same requests, in another order."""
    mix = _mix(name)
    runs = [traffic.schedule(mix, s, 10.0) for s in (1, 2, 3 ** 20)]

    def window(arr):
        return [a for a in arr if a.segment == "window"]
    counts = {len(window(r)) for r in runs}
    assert len(counts) == 1
    for key in ("prompt_len", "output_len"):
        sets = {tuple(sorted(getattr(a, key) for a in window(r)))
                for r in runs}
        assert len(sets) == 1
    orders = {tuple(a.prompt_len for a in window(r)) for r in runs}
    assert len(orders) == 3
    lead = float(mix["lead_in_s"])
    for r in runs:
        assert all(lead <= a.due < lead + 10.0 for a in window(r))
        assert [a.idx for a in r] == list(range(len(r)))
        assert all(x.due <= y.due for x, y in zip(r, r[1:]))


def test_lengths_are_clamped_log_normal_quantiles():
    spec = {"median": 384, "sigma": 0.8, "min": 32, "max": 1536}
    xs = traffic.length_quantiles(spec, 1001)
    assert min(xs) >= 32 and max(xs) <= 1536
    assert sorted(xs)[500] == 384            # the middle quantile
    assert xs == sorted(xs)
    # clamping piles the far tails onto the bounds
    tight = traffic.length_quantiles({**spec, "max": 400}, 101)
    assert tight.count(400) > 1


def test_poisson_count_matches_rate():
    mix = _mix("chat-steady")
    arr = traffic.schedule(mix, 7, 20.0)
    n = sum(a.segment == "window" for a in arr)
    assert n == round(mix["arrival"]["rate_rps"] * 20.0)
    gaps = np.diff([a.due for a in arr if a.segment == "window"])
    # exponential gaps: the coefficient of variation is about 1
    assert 0.7 < gaps.std() / gaps.mean() < 1.3


def test_bursts_land_in_their_phase():
    mix = traffic.scaled(_mix("chat-burst"), 10.0)   # enough arrivals
    b = mix["arrival"]["burst"]
    arr = traffic.schedule(mix, 3, 32.0)
    w0 = float(mix["lead_in_s"])
    win = [a for a in arr if a.segment == "window"]
    phase = collections.Counter(
        ((a.due - w0) % b["period_s"]) < b["length_s"] for a in win)
    burst_share = b["length_s"] * b["rate_rps"] / (
        b["length_s"] * b["rate_rps"]
        + (b["period_s"] - b["length_s"]) * mix["arrival"]["rate_rps"])
    assert phase[True] / len(win) == pytest.approx(burst_share, abs=0.05)


def test_rate_scale_scales_every_rate():
    mix = _mix("chat-burst")
    k = traffic.scaled(mix, 1.5)
    assert k["arrival"]["rate_rps"] == 1.5 * mix["arrival"]["rate_rps"]
    assert k["arrival"]["burst"]["rate_rps"] == \
        1.5 * mix["arrival"]["burst"]["rate_rps"]


def test_gaps_bunch_as_a_poisson_process_does():
    """The seed orders the gaps by a plain permutation: nothing spaces the
    arrivals out, so some runs of eight arrivals come far closer together
    than eight mean gaps, and others far wider apart."""
    mix = traffic.scaled(_mix("chat-steady"), 10.0)   # 16 req/s
    arr = traffic.schedule(mix, 2 ** 31 + 11, 20.0)
    gaps = np.diff([a.due for a in arr if a.segment == "window"])
    eights = np.convolve(gaps, np.ones(8), "valid") / gaps.mean()
    assert eights.min() < 4.0 and eights.max() > 12.0
    rng = np.random.default_rng(0)
    assert sorted(traffic.order(list(range(40)), rng)) == list(range(40))
