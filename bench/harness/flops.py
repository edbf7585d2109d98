"""Model FLOPs of one executed tick, from what its plan carried."""


def tick_flops(ctx, tick) -> float:
    """Decodes at their context lengths plus each prefill chunk's new
    tokens at their positions (FLOPs per token are linear in context, so
    a chunk is its length times the cost at its mean position)."""
    f = ctx.family.flops_per_token
    total = sum(f(ctx.m, c) for c in tick.decode_ctx)
    for start, n in tick.prefill:
        if n > 0:
            total += n * f(ctx.m, start + (n + 1) / 2)
    return total
