"""Engine: the engine's own host milliseconds per tick, from the
program's spans in the trace.

Mean, over the ``dstack.engine.execute`` spans inside the traced
interval, of each span's length less the part its
``dstack.engine.host_sync`` spans cover: the host packing and enqueue of
the plan's dispatches, without the waits for the device. Nothing where
the reduced trace holds none of the program's spans (``reduce_trace``
keeps only ``bench.*`` host spans). Program spans, on the trace's
clock."""
from harness.spans import covered, spans_inside


def read(ctx):
    execs = spans_inside(ctx.trace, "dstack.engine.execute")
    if not execs:
        return None
    syncs = spans_inside(ctx.trace, "dstack.engine.host_sync")
    own = [d - covered(syncs, s, s + d) for _, s, d in execs]
    return sum(own) / len(own) / 1e6
