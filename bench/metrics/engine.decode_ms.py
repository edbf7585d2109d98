"""Engine: device milliseconds per decode dispatch, from the trace.

The decode step is one executable, run once in every tick that decodes:
of the executables that run inside the traced interval's ticks, the one
that runs exactly once in each decoding tick and in no other
(``trace.decode_module``; nothing is read where none or two do). Its mean
device time over those runs."""
from harness import trace as tr
from harness.kernel_time import tick_spans


def read(ctx):
    dev = tr.first_device(ctx.trace)
    if ctx.peaks is None or dev is None or not dev["modules"]:
        return None
    ticks = tick_spans(ctx)
    name = tr.decode_module(dev["modules"], ticks)
    if name is None:
        return None
    runs = [d for (m, _, d), _ in tr.runs_inside(dev["modules"], ticks)
            if m == name]
    return sum(runs) / len(runs) / 1e6
