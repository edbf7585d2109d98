"""Kernel: the SSD scan kernel's share of its roofline, in %: the sum over
the traced interval's prefill dispatches of the least time the chip could
take for the scan's work (``bench/kernels/ssd_scan.py``, times the
layers), over the kernel's device time in those dispatches (its calls as
``harness.kernel_time.kernel_events`` finds them; nothing is read where a
prefill tick holds no run of one call per layer). A segment is what the
dispatch scanned: the new chunk, or the whole prefix where a continuation
recomputes it."""
from harness import trace as tr
from harness.kernel_time import device_ns, kernel_events

CHUNK = 128     # the engine's SSD chunk (ModelConfig.ssm_chunk)


def read(ctx):
    dev = tr.first_device(ctx.trace)
    k = ctx.kernels["ssd_scan"]
    if ctx.peaks is None or dev is None:
        return None
    events = kernel_events(ctx, k)
    ticks = [t for t in ctx.traced_ticks() if t.prefill]
    if not events or not ticks or device_ns(events) <= 0:
        return None
    m = ctx.m
    heads = m["expand"] * m["d_model"] // m["headdim"]
    bound = 0.0
    for t in ticks:
        segs = [(s + n) if ctx.recompute and s > 0 else n
                for s, n in t.prefill if n > 0]
        f, b = k.cost(segs, heads, m["d_state"], m["headdim"], CHUNK)
        bound += max(f / ctx.peaks["bf16_flops_per_s"],
                     b / ctx.peaks["hbm_bytes_per_s"])
    bound *= m[k.LAYERS]
    return 100.0 * bound / (device_ns(events) / 1e9)
