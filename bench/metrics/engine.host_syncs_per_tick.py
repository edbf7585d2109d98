"""Engine: blocking device-to-host reads per executed tick.

The window's change in ``EngineStats.host_syncs`` (every read the engine
and its planner make through ``InferenceEngine.host_sync``: the decode's
token read-back, a prefill's token count, prompt reads of device arrays)
over the ticks that started in the window. Each read holds the host until
the device has run everything before it. Program counter."""


def read(ctx):
    w0, w1 = ctx.stats.get("w0"), ctx.stats.get("w1")
    if not w0 or not w1 or "host_syncs" not in w0:
        return None
    ticks = len(ctx.window_ticks())
    if not ticks:
        return None
    return (w1["host_syncs"] - w0["host_syncs"]) / ticks
