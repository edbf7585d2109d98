"""Client-side arithmetic: percentiles, time to first token, gaps between
tokens, goodput. Pure functions over the benchmark's own stamps."""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Sequence


def percentile(xs: Sequence[float], q: float) -> float:
    """Nearest-rank percentile, q in [0, 1] (as ``repro.serving.metrics``).
    NaN for an empty sample."""
    if not xs:
        return float("nan")
    s = sorted(xs)
    return s[min(len(s) - 1, max(0, math.ceil(q * len(s)) - 1))]


@dataclasses.dataclass
class ClientRecord:
    """What one client saw: its due time, whether the gateway refused it,
    and the host time at which each of its tokens reached it."""
    due: float                      # absolute perf_counter seconds
    in_window: bool
    refused: bool = False
    done: bool = False
    token_times: List[float] = dataclasses.field(default_factory=list)

    def ttft(self, censor_at: float) -> Optional[float]:
        """First-token time minus due time; a request with no token yet
        counts at its wait so far (censored); a refused one has none."""
        if self.refused:
            return None
        if self.token_times:
            return self.token_times[0] - self.due
        return censor_at - self.due

    def gaps(self) -> List[float]:
        t = self.token_times
        return [b - a for a, b in zip(t, t[1:])]


def end_to_end(records: Sequence[ClientRecord], w0: float, w1: float,
               censor_at: float, ttft_limit_s: float,
               tbt_limit_s: float) -> Dict[str, float]:
    """The end-to-end metrics over requests due in [w0, w1).

    ttft_p50_ms, ttft_p95_ms: median and 95th percentile of first-token
    time minus due time, an unfinished request at its censored wait, a
    refused one as missing.
    tbt_p95_ms: 95th percentile of every gap between consecutive tokens of
    every such request. goodput_rps: requests that met both limits (TTFT,
    and every gap under the TBT limit) and completed, over the window.
    output_tok_per_s: tokens any client received inside the window, over
    the window."""
    win = [r for r in records if r.in_window]
    span = w1 - w0
    ttfts = [t for t in (r.ttft(censor_at) for r in win) if t is not None]
    ttfts += [math.inf] * sum(r.refused for r in win)
    gaps = [g for r in win for g in r.gaps()]
    good = 0
    for r in win:
        t = r.ttft(censor_at)
        if (r.done and t is not None and t <= ttft_limit_s
                and all(g <= tbt_limit_s for g in r.gaps())):
            good += 1
    toks = sum(1 for r in records for t in r.token_times if w0 <= t < w1)
    return {
        "ttft_p50_ms": percentile(ttfts, 0.5) * 1e3,
        "ttft_p95_ms": percentile(ttfts, 0.95) * 1e3,
        "tbt_p95_ms": percentile(gaps, 0.95) * 1e3,
        "goodput_rps": good / span,
        "output_tok_per_s": toks / span,
        "attempted": len(win),
        "failed": sum(1 for r in win if r.refused or not r.done),
        "ttft_samples": len(ttfts),
        "tbt_samples": len(gaps),
    }
