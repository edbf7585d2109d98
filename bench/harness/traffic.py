"""Seeded open-loop traffic from a mix file (``bench/traffic/<name>.json``).

One general generator reads every mix. A run's timeline has three
segments: the lead-in, the measured window and the tail that keeps load on
while the window's last requests drain. Each segment holds a fixed number
of arrivals, the integral of the mix's rate over it, and a fixed multiset
of arrival gaps and lengths, the quantiles of the mix's distributions. The
seed only orders them, by a plain random permutation (``order``), and
draws the prompt tokens, so every seed offers the same work in the window,
in another order (a seed that changed the amount of work would spread the
end-to-end metrics by itself).

Arrival maths follows ``repro.serving.traffic`` (exponential gaps for a
Poisson process; a time-varying rate through its cumulative integral), with
the quantiles of the exponential, in a random order, in place of draws:
the arrivals bunch and thin as a Poisson process's do, but every seed's
gaps are the same set.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Dict, List, Sequence

import numpy as np


@dataclasses.dataclass(frozen=True)
class Arrival:
    idx: int            # arrival order over the whole run
    due: float          # seconds after the first due arrival
    prompt_len: int
    output_len: int
    segment: str        # "lead_in" | "window" | "tail"


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Independent numpy stream per purpose; any non-negative seed, however
    large (numpy takes arbitrary-precision integers)."""
    return np.random.default_rng([int(seed), int(stream)])


def rate_at(mix: Dict, t: float, window_start: float) -> float:
    """Offered rate (req/s) at run time ``t``. Bursts are phase-locked to
    the window's start so every run sees the same bursts in its window."""
    arr = mix["arrival"]
    base = float(arr["rate_rps"])
    burst = arr.get("burst")
    if not burst:
        return base
    phase = (t - window_start) % float(burst["period_s"])
    return float(burst["rate_rps"]) if phase < float(burst["length_s"]) \
        else base


def _cum_rate(mix: Dict, t0: float, t1: float, window_start: float,
              step: float = 1e-3) -> np.ndarray:
    ts = np.arange(t0, t1 + step / 2, step)
    r = np.array([rate_at(mix, t, window_start) for t in ts[:-1]])
    return ts, np.concatenate([[0.0], np.cumsum(r * step)])


def length_quantiles(spec: Dict, n: int) -> List[int]:
    """``n`` lengths at the mid-quantiles of a clamped log-normal."""
    nd = NormalDist()
    med, sig = float(spec["median"]), float(spec["sigma"])
    lo, hi = int(spec["min"]), int(spec["max"])
    return [int(min(hi, max(lo, round(med * math.exp(
        sig * nd.inv_cdf((i + 0.5) / n)))))) for i in range(n)]


def _gap_quantiles(n: int) -> np.ndarray:
    """``n`` unit-mean exponential gaps at their mid-quantiles."""
    return -np.log1p(-(np.arange(n) + 0.5) / n)


def order(values: Sequence, rng: np.random.Generator) -> np.ndarray:
    """``values`` in a seeded random order (a plain permutation)."""
    return rng.permutation(np.sort(np.asarray(values)))


def _segment(mix: Dict, seed: int, stream: int, t0: float, t1: float,
             window_start: float, name: str) -> List[Arrival]:
    ts, cum = _cum_rate(mix, t0, t1, window_start)
    n = int(round(cum[-1]))
    if n == 0:
        return []
    rng = rng_for(seed, stream)
    gaps = order(_gap_quantiles(n + 1), rng)
    # n arrivals inside (t0, t1): cumulative positions in rate-integral
    # space, scaled so the n+1 gaps fill the segment exactly
    u = np.cumsum(gaps)[:n] / gaps.sum() * cum[-1]
    due = np.interp(u, cum, ts)
    plen = order(length_quantiles(mix["prompt_tokens"], n), rng)
    olen = order(length_quantiles(mix["output_tokens"], n), rng)
    return [Arrival(0, float(d), int(p), int(o), name)
            for d, p, o in zip(due, plen, olen)]


def schedule(mix: Dict, seed: int, window_s: float,
             rate_scale: float = 1.0) -> List[Arrival]:
    """Every arrival of one run, in due order."""
    if rate_scale != 1.0:
        mix = scaled(mix, rate_scale)
    lead, tail = float(mix["lead_in_s"]), float(mix["drain_max_s"])
    w0, w1 = lead, lead + window_s
    out = (_segment(mix, seed, 1, 0.0, w0, w0, "lead_in")
           + _segment(mix, seed, 2, w0, w1, w0, "window")
           + _segment(mix, seed, 3, w1, w1 + tail, w0, "tail"))
    out.sort(key=lambda a: a.due)
    return [dataclasses.replace(a, idx=i) for i, a in enumerate(out)]


def scaled(mix: Dict, k: float) -> Dict:
    """The mix with every rate multiplied by ``k`` (for a rate sweep)."""
    arr = dict(mix["arrival"])
    arr["rate_rps"] = float(arr["rate_rps"]) * k
    if arr.get("burst"):
        b = dict(arr["burst"])
        b["rate_rps"] = float(b["rate_rps"]) * k
        arr["burst"] = b
    return {**mix, "arrival": arr}


def prompt_tokens(arrivals: Sequence[Arrival], seed: int,
                  vocab: int) -> List[np.ndarray]:
    """Uniform random token ids per arrival, from the seed."""
    rng = rng_for(seed, 4)
    return [rng.integers(0, vocab, size=a.prompt_len, dtype=np.int32)
            for a in arrivals]
