"""Planner: how long requests waited in the planner's queue, in ms.

Median, over the requests due in the window, of the program's own stamps
``scheduled`` (``StepPlanner.build`` first put a chunk of the request into
a plan) minus ``delivered`` (the gateway handed it to the tick server):
the part of TTFT between the gateway and the first prefill. Requests
whose wait spans the profiler's own start or stop are left out. Program
stamps, on the gateway's clock."""
from harness.stamps import intervals
from harness.stats import percentile


def read(ctx):
    waits = intervals(ctx, "delivered", "scheduled")
    return percentile(waits, 0.5) * 1e3 if waits else None
