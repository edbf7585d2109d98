"""The program's own per-request stamps, as the readers take them.

``Request.delivered`` (the gateway handed the request to the tick
server), ``Request.scheduled`` (``StepPlanner.build`` first put a chunk of
it into a plan) and ``Request.first_token`` (its first token was
observed, after the tick's execute) are seconds on the gateway's clock,
whose origin is the drive's ``t0``. A program without a stamp gives no
interval.
"""
from __future__ import annotations

from typing import List


def intervals(ctx, start: str, end: str) -> List[float]:
    """Seconds from stamp ``start`` to stamp ``end`` of every request due
    in the window that carries both; a request whose interval spans the
    profiler's own start or stop, which block the host, is left out."""
    drive, t0 = ctx.drive, ctx.drive.t0
    out = []
    for rid, stream in drive.gw.streams.items():
        rec = drive.records.get(rid)
        if rec is None or not rec.in_window:
            continue
        a = getattr(stream.req, start, -1.0)
        b = getattr(stream.req, end, -1.0)
        if a < 0 or b < 0:
            continue
        if any(s < t0 + b and t0 + a < e for s, e in ctx.stalls):
            continue
        out.append(b - a)
    return out
