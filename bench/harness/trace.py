"""From a ``jax.profiler`` trace to the few lists the metric readers need.

A traced run records a few seconds of steady state. ``reduce_trace``
keeps, per device, every operation the device ran (name, start, duration)
and the host spans the benchmark opened (``bench.*``); everything else in
the trace is dropped. The functions below work on that reduced form, so a
recorded one (``bench/testdata``) checks them without a chip.
"""
from __future__ import annotations

import bisect
import glob
import gzip
import json
import os
import re
import sys
from typing import Dict, List, Optional, Sequence, Tuple

Event = Tuple[str, int, int]        # name, start ns, duration ns

OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"


WINDOW_SPAN = "bench.window"


def _profile(profile_dir: str):
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(profile_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(files) != 1:
        raise RuntimeError(f"expected one trace under {profile_dir}, "
                           f"found {len(files)}")
    return ProfileData.from_file(files[0])


def reduce_trace(profile_dir: str) -> Dict:
    """The reduced trace of the one ``.xplane.pb`` under ``profile_dir``,
    cut to the host span ``bench.window`` that the run opened around the
    interval it measures (the profiler's clock is the trace's own)."""
    pd = _profile(profile_dir)
    marks = [(int(ev.start_ns), int(ev.duration_ns))
             for plane in pd.planes if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events
             if ev.name == WINDOW_SPAN]
    if len(marks) != 1:
        raise RuntimeError(f"expected one {WINDOW_SPAN} span, "
                           f"found {len(marks)}")
    t0_ns, t1_ns = marks[0][0], marks[0][0] + marks[0][1]
    devices: Dict[str, Dict[str, List[Event]]] = {}
    spans: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {OP_LINE: "ops", MODULE_LINE: "modules"}.get(line.name)
                if key is None:
                    continue
                for ev in line.events:
                    s, d = int(ev.start_ns), int(ev.duration_ns)
                    if s + d > t0_ns and s < t1_ns:
                        dev[key].append((ev.name, s, d))
            devices[plane.name] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if (ev.name.startswith("bench.")
                            and ev.name != WINDOW_SPAN):
                        s, d = int(ev.start_ns), int(ev.duration_ns)
                        if s + d > t0_ns and s < t1_ns:
                            spans.append((ev.name, s, d))
    spans.sort(key=lambda e: e[1])
    return {"window": [t0_ns, t1_ns], "devices": devices, "spans": spans}


def describe(profile_dir: str, limit: int = 40) -> Dict:
    """Planes, lines and the commonest event names of a raw trace: what to
    look at before writing code against a trace."""
    out = {}
    for plane in _profile(profile_dir).planes:
        lines = {}
        for line in plane.lines:
            names: Dict[str, List[float]] = {}
            for ev in line.events:
                n = names.setdefault(ev.name, [0, 0.0])
                n[0] += 1
                n[1] += ev.duration_ns / 1e6
            top = sorted(names.items(), key=lambda kv: -kv[1][1])[:limit]
            lines[line.name] = {"events": sum(v[0] for v in names.values()),
                                "top_ms": top}
        out[plane.name] = lines
    return out


def save(reduced: Dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(reduced, f)


def load(path: str) -> Dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


# ------------------------------------------------------------- reductions
def clip(events: Sequence[Event], t0: int, t1: int) -> List[Tuple[int, int]]:
    """Intervals of ``events`` cut to [t0, t1]."""
    out = []
    for _, s, d in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            out.append((a, b))
    return out


def union(intervals: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def busy_ns(events: Sequence[Event], t0: int, t1: int) -> int:
    """Nanoseconds of [t0, t1] in which at least one operation ran."""
    return sum(b - a for a, b in union(clip(events, t0, t1)))


def overlap_ns(a: Sequence[Tuple[int, int]],
               b: Sequence[Tuple[int, int]]) -> int:
    """Total length of the intersection of two unions of intervals."""
    a, b = union(a), union(b)
    i = j = total = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        total += max(0, hi - lo)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_gaps(events: Sequence[Event], spans: Sequence[Event], t0: int,
              t1: int) -> List[Tuple[str, int]]:
    """Every stretch of [t0, t1] with no operation on the device, longest
    first, named by the innermost benchmark host span open at its middle
    ("host.other" where none is)."""
    busy = union(clip(events, t0, t1))
    gaps, at = [], t0
    for a, b in busy + [(t1, t1)]:
        if a > at:
            gaps.append((at, a))
        at = max(at, b)
    out = []
    for a, b in gaps:
        mid = (a + b) // 2
        label = "host.other"
        best = None
        for name, s, d in spans:
            if s <= mid < s + d and (best is None or d < best):
                label, best = name, d
        out.append((label, b - a))
    out.sort(key=lambda g: -g[1])
    return out


def op_totals(events: Sequence[Event], t0: int, t1: int
              ) -> List[Tuple[str, int]]:
    """Device nanoseconds per operation name inside [t0, t1], largest
    first."""
    tot: Dict[str, int] = {}
    for name, s, d in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            tot[name] = tot.get(name, 0) + (b - a)
    return sorted(tot.items(), key=lambda kv: -kv[1])


def matching(events: Sequence[Event], needles: Sequence[str]
             ) -> List[Event]:
    """Events whose name contains any of ``needles``."""
    return [e for e in events if any(n in e[0] for n in needles)]


Tick = Tuple[int, int, bool]        # start ns, end ns, whether it decoded


def _refuse(why: str) -> None:
    """A reading that cannot be trusted is left out, with the reason."""
    print(f"bench: trace: {why}", file=sys.stderr)
    return None


def runs_inside(modules: Sequence[Event], ticks: Sequence[Tick]
                ) -> List[Tuple[Event, int]]:
    """The module runs whose middle lies inside one of ``ticks``, each
    with that tick's index (the device's clock and the host's differ by
    up to a millisecond in a trace, so a run can start just before the
    host span that launched it)."""
    out = []
    for ev in modules:
        _, s, d = ev
        for i, (a, b, _) in enumerate(ticks):
            if a <= s + d // 2 < b:
                out.append((ev, i))
                break
    return out


def decode_module(modules: Sequence[Event], ticks: Sequence[Tick]
                  ) -> Optional[str]:
    """The executable that runs once per decode dispatch: the one with
    exactly one run inside each tick that decoded and none inside any
    other tick (every engine executable is a ``<lambda>`` to the trace, so
    where it runs is what names it). Where several qualify, the one with
    the most device time, unless the second has a tenth of that or more
    (the step split in two): then, or where none qualifies, None."""
    per: Dict[str, Dict[int, int]] = {}
    busy: Dict[str, int] = {}
    for (name, _, d), i in runs_inside(modules, ticks):
        c = per.setdefault(name, {})
        c[i] = c.get(i, 0) + 1
        busy[name] = busy.get(name, 0) + d
    want = {i for i, t in enumerate(ticks) if t[2]}
    if not want:
        return _refuse("no tick in the traced interval decoded")
    ok = sorted((n for n, c in per.items()
                 if set(c) == want and all(v == 1 for v in c.values())),
                key=lambda n: -busy[n])
    if not ok:
        return _refuse(f"no executable runs once in each of the "
                       f"{len(want)} decoding ticks")
    if len(ok) > 1 and busy[ok[1]] >= 0.1 * busy[ok[0]]:
        return _refuse(f"two executables run once per decode: {ok[0]}, "
                       f"{ok[1]}")
    return ok[0]


def kernel_calls(ops: Sequence[Event], modules: Sequence[Event],
                 ticks: Sequence[Tick], decode: str, inside: bool,
                 calls: int, need: Sequence[int]) -> Optional[List[Event]]:
    """The calls of one kernel: ``ops`` (its candidates) inside the runs,
    within ``ticks``, of the decode executable (``inside``) or of any other
    executable. Each such run that holds any must hold exactly ``calls``
    (one per layer), all of one op name, and each tick indexed in ``need``
    must hold such a run: else None, since the reading would mix kernels
    or miss some."""
    runs = sorted((s, s + d, n, i) for (n, s, d), i in
                  runs_inside(modules, ticks) if (n == decode) == inside)
    starts = [r[0] for r in runs]
    held: Dict[int, List[Event]] = {}
    for op in ops:
        k = bisect.bisect_right(starts, op[1]) - 1
        if k >= 0 and op[1] < runs[k][1]:
            held.setdefault(k, []).append(op)
    out = []
    for k, evs in sorted(held.items()):
        names = {e[0] for e in evs}
        if len(evs) != calls or len(names) != 1:
            return _refuse(f"a run of {runs[k][2]} holds {len(evs)} "
                           f"candidate calls of {len(names)} ops, not "
                           f"{calls} of one")
        out.extend(evs)
    missing = set(need) - {runs[k][3] for k in held}
    if missing:
        return _refuse(f"{len(missing)} of {len(set(need))} ticks hold no "
                       f"run with the kernel's calls")
    return out


def short(name: str) -> str:
    """An HLO op's name, kind and result type, without its operands:
    "%closed_call.9 custom-call bf16[64,16,1,128] tpu_custom_call"."""
    m = re.match(r"(%[\w.\-]+) = (\(?\w+\[[^\]]*\])?.*?\s([\w\-]+)\(",
                 name)
    if not m:
        return name[:96]
    out = " ".join(g for g in (m.group(1), m.group(3), m.group(2)) if g)
    return out + (" tpu_custom_call" if "tpu_custom_call" in name else "")


def first_device(reduced: Dict) -> Optional[Dict]:
    devs = reduced["devices"]
    return devs[sorted(devs)[0]] if devs else None
