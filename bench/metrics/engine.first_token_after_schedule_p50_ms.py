"""Engine: from the first prefill chunk to the first token, in ms.

Median, over the requests due in the window, of the program's own stamps
``first_token`` (the planner observed the request's first token, after
the execute of the tick that made it) minus ``scheduled`` (the plan that
carried its first chunk was built): the prefill chunks, the tick the
first decode waits for, and the ticks between. Requests whose interval
spans the profiler's own start or stop are left out. Program stamps, on
the gateway's clock."""
from harness.stamps import intervals
from harness.stats import percentile


def read(ctx):
    xs = intervals(ctx, "scheduled", "first_token")
    return percentile(xs, 0.5) * 1e3 if xs else None
