"""Whole step: model FLOPs the traced interval's ticks computed, over the
executed ticks' wall time times the chip's peak, in %.

Model FLOPs per token are 2 per weight of every matmul plus attention over
the token's context (``flops_per_token`` of the configuration's reference);
a prefill chunk counts its new tokens only, a recomputed prefix nothing.
Tick wall time is ``execute`` on the host clock, which ends in the
decode's read-back. Peak from bench/peaks.json."""
from harness.flops import tick_flops


def read(ctx):
    if ctx.peaks is None:
        return None
    ticks = ctx.traced_ticks()
    wall = sum(t.end - t.start for t in ticks)
    if not ticks or wall <= 0:
        return None
    flops = sum(tick_flops(ctx, t) for t in ticks)
    return 100.0 * flops / (wall * ctx.peaks["bf16_flops_per_s"])
