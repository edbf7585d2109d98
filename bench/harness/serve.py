"""The system under test, as the benchmark drives it: one
``InferenceEngine`` with its slots, a ``StepPlanner`` and an
``AsyncGateway`` on the wall clock. The program is used as it stands; the
benchmark only wraps a few of its instances' methods to stamp host time
(and, in a traced run, to open profiler spans), and reads what clients
receive from the gateway's token streams.
"""
from __future__ import annotations

import asyncio
import dataclasses
import time
from typing import Dict, List, Optional, Tuple

import jax
import numpy as np

from harness.stats import ClientRecord
from harness.traffic import Arrival


# ---------------------------------------------------------------- buckets
# The engine's shape buckets, as ``repro.serving.engine`` defines them:
# packed prefill rows bucket their token count to {2^k, 3 * 2^(k-1)}, the
# longest segment (the row length) and the segment count to powers of two.
def pow2_at_least(n: int) -> int:
    return 1 << max(0, int(n) - 1).bit_length()


def packed_bucket(n: int) -> int:
    p = pow2_at_least(n)
    half = 3 * p // 4
    return half if half >= n else p


def _witness(n: int, m: int, s: int, a: int) -> Optional[List[int]]:
    """Lengths of an admission batch of ``n`` prompts whose longest is
    ``m`` and whose sum is ``s``: n - 1 whole prompts of at least ``a``
    tokens and a last, possibly truncated, chunk of at least 1."""
    if n == 1:
        return [m] if s == m else None
    lens = [m] + [a] * (n - 2) + [1]
    if m < a or sum(lens) > s or n * m < s:
        return None
    left = s - sum(lens)
    for i in range(1, n):
        add = min(left, m - lens[i])
        lens[i] += add
        left -= add
    return lens if left == 0 else None


def admission_batches(chunk: int, p_min: int, p_max: int, n_slots: int,
                      slot_len: int, recompute: bool) -> List[List[int]]:
    """One batch of first-chunk lengths for every packed-prefill shape an
    admission tick can reach, and one for every batch size ``n`` (the
    engine's slot-mask update is shaped by it).

    A tick admits whole prompts (p_min..p_max tokens) and then truncates
    the last to what is left of the ``chunk`` budget, so a batch sums to at
    most ``chunk``. A prompt longer than the budget continues in later
    ticks; with lazy reservation off at most one request is mid-prefill at
    a time, so a continuation carries one segment: its new tokens
    (incremental engines, warmed apart) or its whole prefix
    (``recompute``), which the one-segment batches up to p_max cover."""
    one_max = min(p_max if recompute else min(p_max, chunk), slot_len - 1)
    seen: Dict[Tuple[int, int, int], List[int]] = {}
    for ln in range(1, one_max + 1):
        seen.setdefault((packed_bucket(ln), pow2_at_least(ln), 1), [ln])
    out = list(seen.values())
    bmax = min(p_max, chunk)
    buckets = sorted({packed_bucket(i) for i in range(1, chunk + 1)})
    n = 2
    while n <= n_slots and (n - 1) * p_min + 1 <= chunk:
        before = len(out)
        for k in range(slot_len.bit_length() + 1):
            r = 1 << k
            mlo, mhi = max(r // 2 + 1, p_min), min(r, bmax)
            for t_prev, t in zip([0] + buckets, buckets):
                key = (t, r, pow2_at_least(n))
                if key in seen:
                    continue
                for s in range(max(t_prev + 1, mlo + (n - 2) * p_min + 1),
                               min(t, n * mhi) + 1):
                    lens = next((w for m in range(mlo, mhi + 1)
                                 for w in [_witness(n, m, s, p_min)] if w),
                                None)
                    if lens:
                        seen[key] = lens
                        out.append(lens)
                        break
        if len(out) == before:
            out.append([p_min] * (n - 1) + [1])
        n += 1
    return out


# ----------------------------------------------------------------- engine
def build_engine(cfg, weights, serving: Dict):
    """The engine over the benchmark's weights, with its slots."""
    from repro.models.registry import build_model
    from repro.serving.engine import InferenceEngine
    eng = InferenceEngine(build_model(cfg), weights,
                          cache_len=serving["cache_len"])
    eng.init_slots(serving["n_slots"], serving["cache_len"], paged=True,
                   page_size=serving["page_size"],
                   total_pages=serving.get("total_pages") or None)
    if serving.get("prefix_cache") and eng.prefix_cache_capable():
        eng.enable_prefix_cache()
    return eng


def warm(eng, serving: Dict, mix: Dict) -> Dict[str, int]:
    """Run every executable the cell's traffic can reach once, through the
    engine's own admission, continuation, decode and free paths, then
    return the engine to its empty state."""
    chunk = int(serving["planner"]["chunk_tokens"])
    if chunk <= 0:
        raise ValueError("the warm-up needs a finite chunk_tokens budget: "
                         "an unbounded admission batch reaches unboundedly "
                         "many packed-prefill shapes")
    p_min = int(mix["prompt_tokens"]["min"])
    p_max = int(mix["prompt_tokens"]["max"])
    recompute = not eng.chunk_capable()
    batches = admission_batches(chunk, p_min, p_max, eng.n_slots,
                                eng.slot_len, recompute)

    def prompt(n):
        return {"tokens": np.zeros((1, n), np.int32)}

    for lens in batches:
        slots = eng.insert_many([prompt(n) for n in lens],
                                n_tokens=[1] * len(lens))
        eng.step(slots)
        for s in slots:
            eng.free(s)
    conts = set()
    if not recompute:
        # incremental continuations: one segment of c new tokens after a
        # resident prefix
        for c in range(1, min(chunk, p_max) + 1):
            key = (packed_bucket(c), pow2_at_least(c))
            if key in conts:
                continue
            conts.add(key)
            (slot,) = eng.insert_many([prompt(1)], n_tokens=[c + 1])
            eng.chunk_append([(slot, prompt(1 + c), True)])
            eng.free(slot)
    eng.warm_prefix_ops()
    jax.block_until_ready(_copy(eng._last_tok))
    eng.release_all_slots()
    eng.reset_stats()
    return {"admission_batches": len(batches), "continuations": len(conts)}


# ---------------------------------------------------------------- driving
# a copy of the engine's pending-token row: the prefill's argmax, which the
# engine feeds to the slot's first decode but never emits
_copy = jax.jit(lambda a: a + 0)


@dataclasses.dataclass
class TickRecord:
    """One executed tick, in a traced run."""
    start: float
    end: float
    build_s: float
    decode_ctx: List[int]           # positions attended by each decode
    prefill: List[Tuple[int, int]]  # (first position, new tokens) per chunk
    admitted: List[int]             # rids whose first chunk ran
    observe_s: float = 0.0


class Drive:
    """One run of the gateway over a schedule of arrivals.

    Clients are coroutines, one per request, that stamp the host clock at
    each token they receive. Wrappers on the tick server, the planner and
    the engine stamp delivery, plan building, execution and observation;
    with ``trace`` they also open ``jax.profiler.TraceAnnotation`` spans,
    which land on the device trace's clock."""

    def __init__(self, eng, planner_cfg: Dict, model: str, trace: bool):
        from repro.serving.gateway import AsyncGateway
        from repro.serving.plan import PlannerConfig, StepPlanner
        from repro.serving.request import RequestQueue
        self.eng = eng
        self.trace = trace
        self.model = model
        self.planner = StepPlanner(eng, RequestQueue(model, slo=1e9),
                                   PlannerConfig(**planner_cfg))
        self.gw = AsyncGateway(self.planner, wall_clock=True,
                               max_ticks=10 ** 9)
        self.delivered: Dict[int, float] = {}
        self.ticks: List[TickRecord] = []
        self.seeds: Dict[int, Tuple[object, int]] = {}  # rid -> (row, slot)
        self.records: Dict[int, ClientRecord] = {}
        self._build_s = 0.0
        self._wrap()

    def _span(self, name):
        return jax.profiler.TraceAnnotation(name) if self.trace else _NULL

    def _wrap(self):
        planner, eng, server = self.planner, self.eng, self.gw.server
        build, observe = planner.build, planner.observe
        execute, deliver = eng.execute, server.deliver

        def w_deliver(req):
            self.delivered[req.rid] = time.perf_counter()
            with self._span("bench.deliver"):
                return deliver(req)

        def w_build(now):
            t = time.perf_counter()
            with self._span("bench.plan"):
                plan = build(now)
            self._build_s = time.perf_counter() - t
            return plan

        def w_execute(plan):
            rec = None
            if self.trace:
                rec = TickRecord(
                    time.perf_counter(), 0.0, self._build_s,
                    [eng.slot_pos(s) + 1 for s in plan.decodes],
                    [(c.start, c.length) for c in plan.admissions],
                    [c.rid for c in plan.admissions
                     if c.slot is None])
            with self._span("bench.execute"):
                res = execute(plan)
            finals = [(c.rid, c.slot if c.slot is not None
                       else res.admitted.get(c.rid))
                      for c in plan.admissions if c.final]
            finals = [(r, s) for r, s in finals if s is not None]
            if finals:
                row = _copy(eng._last_tok)
                for rid, slot in finals:
                    self.seeds[rid] = (row, slot)
            if rec is not None:
                rec.end = time.perf_counter()
                self.ticks.append(rec)
            return res

        def w_observe(res, now):
            t = time.perf_counter()
            with self._span("bench.observe"):
                out = observe(res, now)
            if self.trace and self.ticks:
                self.ticks[-1].observe_s = time.perf_counter() - t
            return out

        server.deliver = w_deliver
        planner.build = w_build
        planner.observe = w_observe
        eng.execute = w_execute

    def close(self) -> None:
        """Unwrap the engine (the planner and server die with this run)
        and return it to its empty state."""
        del self.eng.execute
        self.eng.release_all_slots()
        self.eng.reset_stats()

    async def _client(self, stream, rec: ClientRecord):
        async for _ in stream:
            rec.token_times.append(time.perf_counter())
        rec.done = stream.state == "completed"

    async def _run(self, arrivals: List[Arrival], prompts, w0: float,
                   w1: float, end: float, during=None):
        from repro.serving.request import Request
        reqs = [Request(arrival=a.due, rid=a.idx, model=self.model, slo=1e9,
                        n_tokens=a.output_len, prompt_len=a.prompt_len)
                for a in arrivals]
        self.gw.schedule(reqs, {a.idx: {"tokens": p[None, :]}
                                for a, p in zip(arrivals, prompts)})
        run = asyncio.ensure_future(self.gw.run())
        await asyncio.sleep(0)
        t0 = self.t0 = self.gw._t0
        self.w0, self.w1 = t0 + w0, t0 + w1
        self.records = {a.idx: ClientRecord(t0 + a.due,
                                            a.segment == "window")
                        for a in arrivals}
        clients = [asyncio.ensure_future(
            self._client(self.gw.streams[a.idx], self.records[a.idx]))
            for a in arrivals]
        if during is not None:
            await during(self)
        # drain: until every window request has ended, or the tail is over
        window = [a.idx for a in arrivals if a.segment == "window"]
        while time.perf_counter() < t0 + end:
            if all(self.gw.streams[i].state is not None for i in window):
                break
            await asyncio.sleep(0.02)
        self.drain_end = time.perf_counter()
        run.cancel()
        for c in clients:
            c.cancel()
        await asyncio.gather(run, *clients, return_exceptions=True)

    def run(self, arrivals, prompts, w0, w1, end, during=None) -> "Drive":
        """Serve ``arrivals`` (due times relative to the gateway's start);
        the window is [w0, w1), the drain ends by ``end`` at the latest.
        ``during(drive)`` is awaited once the gateway runs."""
        asyncio.run(self._run(arrivals, prompts, w0, w1, end, during))
        return self

    def seed_tokens(self, rids) -> Dict[int, int]:
        """The first (never emitted) token of each of ``rids``."""
        return {rid: int(np.asarray(self.seeds[rid][0])[self.seeds[rid][1]])
                for rid in rids if rid in self.seeds}


class _Null:
    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


_NULL = _Null()
