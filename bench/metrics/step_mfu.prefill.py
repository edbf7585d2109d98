"""Whole step, for the first token: model FLOPs of the traced interval's
ticks that carried a prefill chunk, over those ticks' wall time times the
chip's peak, in %. A prompt's first token waits on these ticks, so this
bounds what a faster prefill kernel can give TTFT. Counted as
``step_mfu`` counts."""
from harness.flops import tick_flops


def read(ctx):
    if ctx.peaks is None:
        return None
    ticks = [t for t in ctx.traced_ticks() if any(n for _, n in t.prefill)]
    wall = sum(t.end - t.start for t in ticks)
    if not ticks or wall <= 0:
        return None
    flops = sum(tick_flops(ctx, t) for t in ticks)
    return 100.0 * flops / (wall * ctx.peaks["bf16_flops_per_s"])
