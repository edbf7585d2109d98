"""Planner: host milliseconds per executed tick spent in
``StepPlanner.build`` and ``StepPlanner.observe``, from the benchmark's
stamps around them, over the ticks of the traced interval. The device waits for
the next tick's plan while they run. Host clock."""


def read(ctx):
    ticks = ctx.traced_ticks()
    if not ticks:
        return None
    return sum(t.build_s + t.observe_s for t in ticks) / len(ticks) * 1e3
