"""Gateway: how late requests reached the planner.

95th percentile, in ms, over the requests due in the window, of the host
time at which the tick server delivered a request to the planner minus
the time it was due. The gateway delivers arrivals only between ticks, so
a long synchronous ``execute`` holds them back; this is the part of TTFT
spent before the queue. Requests whose wait spans the profiler's own
start or stop (which block the host) are left out. Host clock."""
from harness.stats import percentile


def read(ctx):
    rec = ctx.drive.records
    lags = [t - rec[rid].due for rid, t in ctx.drive.delivered.items()
            if rec[rid].in_window and not any(
                a < t and rec[rid].due < b for a, b in ctx.stalls)]
    return percentile(lags, 0.95) * 1e3 if lags else None
