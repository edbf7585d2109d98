"""Kernel: the paged decode attention kernel's share of its roofline, in
%: the sum over the traced interval's decode dispatches of the least time
the chip could take for the work (``bench/kernels/paged_decode.py``, the
larger of FLOPs over peak FLOP/s and bytes over peak bandwidth, times the
layers), over the kernel's device time in those dispatches (its calls as
``harness.kernel_time.kernel_events`` finds them; nothing is read where
the trace does not show one call per layer in every decode step)."""
from harness import trace as tr
from harness.kernel_time import device_ns, kernel_events


def read(ctx):
    dev = tr.first_device(ctx.trace)
    k = ctx.kernels["paged_decode"]
    if ctx.peaks is None or dev is None:
        return None
    events = kernel_events(ctx, k)
    ticks = [t for t in ctx.traced_ticks() if t.decode_ctx]
    if not events or not ticks or device_ns(events) <= 0:
        return None
    m = ctx.m
    heads = m["num_attention_heads"]
    bound = 0.0
    for t in ticks:
        f, b = k.cost(t.decode_ctx, heads, m["num_key_value_heads"],
                      m["hidden_size"] // heads)
        bound += max(f / ctx.peaks["bf16_flops_per_s"],
                     b / ctx.peaks["hbm_bytes_per_s"])
    bound *= m[k.LAYERS]
    return 100.0 * bound / (device_ns(events) / 1e9)
