"""Device time of one kernel in a reduced trace."""
from harness import trace as tr


def tick_spans(ctx):
    """The traced interval's executed ticks on the trace's clock."""
    return [(ctx.to_ns(t.start), ctx.to_ns(t.end), bool(t.decode_ctx))
            for t in ctx.traced_ticks()]


def kernel_events(ctx, kernel):
    """The calls of ``kernel`` (a bench/kernels module) in the traced
    ticks: ops whose name holds one of its ``NAMES``, one per layer (its
    ``LAYERS`` key of the configuration) in the run of the decode step of
    every decoding tick (``DISPATCH`` "decode"), or in a run of another
    executable in every tick that carried a prefill chunk ("prefill"). None
    where the trace does not show exactly that
    (``trace.decode_module``, ``trace.kernel_calls``): a reading that
    would mix kernels or miss calls is left out, not made."""
    dev = tr.first_device(ctx.trace)
    if dev is None:
        return None
    ticks = tick_spans(ctx)
    decode = tr.decode_module(dev["modules"], ticks)
    if decode is None:
        return None
    inside = kernel.DISPATCH == "decode"
    need = [i for i, t in enumerate(ctx.traced_ticks())
            if (t.decode_ctx if inside else any(n for _, n in t.prefill))]
    return tr.kernel_calls(tr.matching(dev["ops"], kernel.NAMES),
                           dev["modules"], ticks, decode, inside,
                           int(ctx.m[kernel.LAYERS]), need)


def device_ns(events) -> int:
    return sum(d for _, _, d in events)
