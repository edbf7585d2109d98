"""Gateway: the gateway's own host milliseconds per tick, from the
program's spans in the trace.

The ``dstack.gateway.pump`` (moving emitted tokens into client streams)
and ``dstack.gateway.deliver`` (handing an arrival to the tick server)
time inside the traced interval, over its ``dstack.tick`` spans. The
device waits while the gateway loop runs them between ticks. Nothing
where the reduced trace holds none of the program's spans
(``reduce_trace`` keeps only ``bench.*`` host spans). Program spans, on
the trace's clock."""
from harness.spans import spans_inside


def read(ctx):
    ticks = spans_inside(ctx.trace, "dstack.tick")
    if not ticks:
        return None
    gw = [d for name in ("dstack.gateway.pump", "dstack.gateway.deliver")
          for _, _, d in spans_inside(ctx.trace, name)]
    return sum(gw) / len(ticks) / 1e6
