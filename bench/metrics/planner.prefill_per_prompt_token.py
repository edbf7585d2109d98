"""Planner and prefix cache: prompt tokens the engine prefilled
(``EngineStats.prefill_tokens``) per prompt token admitted, over the
window. 1.0 when every prompt is computed once; below 1 where the prefix
cache serves part of a prompt; above 1 where a continuation recomputes its
prefix. A count."""


def read(ctx):
    w0, w1 = ctx.stats.get("w0"), ctx.stats.get("w1")
    if not w0 or not w1:
        return None
    admitted = sum(ctx.arrivals[rid].prompt_len
                   for t in ctx.window_ticks() for rid in t.admitted)
    if not admitted:
        return None
    return (w1["prefill_tokens"] - w0["prefill_tokens"]) / admitted
