"""Device: share of the loaded part of the traced interval, in %, in
which no operation ran on the chip. Loaded is the time in which at least
one request had reached the planner and not yet received its last token;
an idle chip with nothing to serve is not counted."""
from harness import trace as tr


def read(ctx):
    dev = tr.first_device(ctx.trace)
    if ctx.peaks is None or dev is None or not dev["ops"]:
        return None
    t0, t1 = ctx.trace["window"]
    rec = ctx.drive.records
    loaded = []
    for rid, t in ctx.drive.delivered.items():
        r = rec[rid]
        end = r.token_times[-1] if r.token_times else ctx.drive.drain_end
        a, b = max(ctx.to_ns(t), t0), min(ctx.to_ns(end), t1)
        if b > a:
            loaded.append((a, b))
    span = sum(b - a for a, b in tr.union(loaded))
    if span <= 0:
        return None
    busy = tr.overlap_ns(tr.clip(dev["ops"], t0, t1), loaded)
    return 100.0 * (1 - busy / span)
