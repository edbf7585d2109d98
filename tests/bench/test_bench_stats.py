"""Client-side arithmetic: nearest-rank percentiles, censored first-token
times, goodput against both limits."""
import math

import pytest

import benchpath  # noqa: F401
from harness.stats import ClientRecord, end_to_end, percentile


def test_nearest_rank_percentile():
    xs = list(range(1, 101))
    assert percentile(xs, 0.95) == 95
    assert percentile(xs, 0.5) == 50
    assert percentile([3.0], 0.95) == 3.0
    assert percentile(list(reversed(xs)), 1.0) == 100
    assert math.isnan(percentile([], 0.5))


def _rec(due, times, done=True, window=True, refused=False):
    return ClientRecord(due, window, refused, done, list(times))


def test_ttft_censors_unfinished_and_drops_refused():
    recs = [_rec(0.0, [0.1, 0.2]), _rec(1.0, [], done=False),
            _rec(2.0, [], done=False, refused=True)]
    assert recs[0].ttft(10.0) == pytest.approx(0.1)
    assert recs[1].ttft(10.0) == pytest.approx(9.0)   # waited until 10 s
    assert recs[2].ttft(10.0) is None


def test_end_to_end_counts_window_only():
    recs = [
        _rec(0.0, [0.05, 0.10, 0.15]),                 # good
        _rec(0.5, [1.0, 1.05]),                        # TTFT 0.5 s: late
        _rec(1.0, [1.1, 1.5]),                         # a 0.4 s gap: late
        _rec(1.5, [1.6], done=False),                  # unfinished
        _rec(2.0, [], done=False, refused=True),       # refused
        _rec(-1.0, [0.0, 0.01], window=False),         # before the window
    ]
    e = end_to_end(recs, 0.0, 2.5, 3.0, ttft_limit_s=0.2, tbt_limit_s=0.3)
    assert e["attempted"] == 5
    assert e["failed"] == 2
    assert e["goodput_rps"] == pytest.approx(1 / 2.5)
    # TTFTs 0.05, 0.5, 0.1, 0.1 and the refused one as missing (inf)
    assert e["ttft_p95_ms"] == math.inf
    # every gap of window requests: 0.05, 0.05, 0.05, 0.4
    assert e["tbt_p95_ms"] == pytest.approx(400.0)
    # tokens received inside [0, 2.5): 3 + 2 + 2 + 1, plus 2 from the
    # request due before the window
    assert e["output_tok_per_s"] == pytest.approx(10 / 2.5)


def test_ttft_percentile_over_censored_waits():
    recs = [_rec(float(i), [i + 0.1]) for i in range(19)]
    recs.append(_rec(19.0, [], done=False))
    e = end_to_end(recs, 0.0, 20.0, 25.0, 1.0, 1.0)
    assert e["ttft_p95_ms"] == pytest.approx(100.0)
    recs.append(_rec(19.5, [], done=False))
    e = end_to_end(recs, 0.0, 20.0, 25.0, 1.0, 1.0)
    assert e["ttft_p95_ms"] == pytest.approx(5500.0)
