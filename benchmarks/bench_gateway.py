"""Gateway traffic benchmark: admission policies under production traffic.

The ISSUE 10 acceptance run: one seeded burst + mixed-SLO trace
(``repro.serving.traffic``) is served through the async gateway
(``repro.serving.gateway``) under two admission policies over the SAME
warmed engine —

  temporal   strict FIFO admission (tiers off — every pre-PR-10 plane)
  dstack     weighted tiers + per-tenant deficit fairness
             (``PlannerConfig.tiers``)

— and the bench reports goodput, per-tier SLO attainment, per-tenant
Jain fairness, and shed/abort rates for each. The trace floods one
tenant's batch-tier work mid-run, so under FIFO the flood queues ahead
of every later interactive arrival; under tiers it cannot. The quick
pass ASSERTS the acceptance criteria: tiered interactive-tier
attainment strictly above FIFO's at equal offered load, per-tenant Jain
no worse, and zero recompiles across the measured virtual runs (the
wall pass may trace a bounded handful of first-seen prefill packings —
host pacing decides how prefills pack — never the decode path).

A wall-clock pass then re-serves the same trace with
``AsyncGateway(wall_clock=True)`` and a ``TraceRecorder`` attached (SLOs
relaxed — CPU-host ticks run an order of magnitude slower than the 1ms
virtual tick, so real-time deadlines would reject the trace): streams
must stay BIT-EXACT with the virtual-clock run, and the ring must hold
a valid trace with one ``dstack.tick`` span per executed tick.

CLI: ``PYTHONPATH=src python benchmarks/bench_gateway.py [--quick|--full]
[--json [PATH]]``; also wired into ``benchmarks/run.py`` as
``bench_gateway``.
"""
from __future__ import annotations

import time

try:                      # package context (benchmarks/run.py)
    from benchmarks import common as _common
except ImportError:       # script context (python benchmarks/bench_gateway.py)
    import common as _common

MODEL = "olmo-1b"
CACHE_LEN = 32
N_SLOTS = 4
PAGE = 8


def _build_engine():
    from repro.configs import get_config
    from repro.serving.engine import make_engine

    cfg = get_config(MODEL).reduced()
    eng = make_engine(cfg, cache_len=CACHE_LEN).init_slots(
        N_SLOTS, paged=True, page_size=PAGE)
    eng.alloc_chips = 1                   # roofline rows need a chip count
    return cfg, eng


def _burst_cfg(quick: bool):
    from repro.serving.traffic import TrafficConfig

    return TrafficConfig(
        model=MODEL,
        duration=0.2 if quick else 0.6,
        rate=240.0,
        seed=12,
        slo_unit=1e-3,                    # calibrated to the 1ms tick
        prompt_tokens=(4, 12),
        gen_tokens=(3, 8))


def _reset_trace(reqs):
    for r in reqs:
        r.state = "pending"
        r.finish = -1.0
        r.first_token = -1.0
        r.tokens_out = 0


def _serve(cfg, eng, reqs, prompts, *, tiers=None, wall=False,
           telemetry=None):
    """One gateway serve of the trace; returns (streams, planner, gw,
    wall seconds)."""
    from repro.serving.gateway import AsyncGateway
    from repro.serving.plan import PlannerConfig, StepPlanner
    from repro.serving.request import RequestQueue

    _reset_trace(reqs)
    eng.release_all_slots()
    eng.reset_stats()
    planner = StepPlanner(eng, RequestQueue(cfg.name, slo=1e9),
                          PlannerConfig(gen_len=4, tiers=tiers))
    planner.telemetry = telemetry
    gw = AsyncGateway(planner, wall_clock=wall, stall_limit=100)
    t0 = time.perf_counter()
    streams = gw.serve_trace(reqs, prompts)
    wall_s = time.perf_counter() - t0
    assert not gw.truncated, "gateway serve hit the max_ticks backstop"
    assert eng.free_pages == eng.total_pages, "gateway serve leaked pages"
    return streams, planner, gw, wall_s


def _wall_serve(cfg, eng, reqs, prompts, *, tiers, telemetry=None):
    """Wall-clock serve with SLOs relaxed: a CPU-host tick takes
    ~10-30ms real against the 1ms virtual tick, so real-time deadlines
    would reject nearly every request the virtual run admitted — the
    wall pass validates pacing, spans and streams, not attainment."""
    slos = [r.slo for r in reqs]
    for r in reqs:
        r.slo = 1e9
    try:
        return _serve(cfg, eng, reqs, prompts, tiers=tiers, wall=True,
                      telemetry=telemetry)
    finally:
        for r, slo in zip(reqs, slos):
            r.slo = slo


def _score(reqs, planner, gw):
    """Per-policy scorecard over the trace's stamped outcomes."""
    from repro.serving.traffic import attainment_by, offered_by

    q = planner.queue
    ontime = sum(1 for r in reqs
                 if r.state == "completed" and 0 <= r.finish <= r.deadline)
    horizon = max(gw.now, 1e-9)
    return {
        "goodput_rps": ontime / horizon,
        "attainment_by_tier": attainment_by(reqs, "tier"),
        "attainment_by_tenant": attainment_by(reqs, "tenant"),
        "offered_by_tier": offered_by(reqs, "tier"),
        "tenant_jain": planner.metrics.tenant_fairness(),
        "completed": q.completed,
        "shed": q.shed,
        "dropped": q.dropped,
        "deadline_aborted": q.deadline_aborted,
        "late": q.late,
        "ticks": gw.server.ticks,
    }


def run_with_results(quick: bool = True):
    """Serve the burst trace under both policies plus the wall-clock
    pass; returns (rows, {policy: score})."""
    from repro.serving.telemetry import (Telemetry, TraceRecorder,
                                         validate_chrome_trace)
    from repro.serving.traffic import (TIER_WEIGHTS, burst_trace,
                                       offered_by, synth_prompts)

    cfg, eng = _build_engine()
    tcfg = _burst_cfg(quick)
    reqs = burst_trace(tcfg, burst_mult=16.0)
    prompts = synth_prompts(reqs, vocab=cfg.vocab_size, seed=0)
    offered = offered_by(reqs, "tier")
    t0 = time.time()
    rows = [("gateway/trace", 0.0,
             f"burst x16, {len(reqs)} requests over {tcfg.duration}s "
             f"virtual ({' '.join(f'{k}={v}' for k, v in sorted(offered.items()))})")]

    policies = [("temporal", None), ("dstack", dict(TIER_WEIGHTS))]
    # warm every executable both admission orders reach — plus a
    # wall-clock pass, whose host-paced arrival floods produce batch
    # shapes the virtual passes never form — then freeze
    for _, tiers in policies:
        _serve(cfg, eng, reqs, prompts, tiers=tiers)
    _wall_serve(cfg, eng, reqs, prompts, tiers=dict(TIER_WEIGHTS))
    rows.append(("gateway/build_warm_s", (time.time() - t0) * 1e6,
                 f"engine + both policy passes warmed"))
    jit_before = eng.jit_cache_sizes()

    scores = {}
    streams_by_policy = {}
    for name, tiers in policies:
        streams, planner, gw, wall_s = _serve(cfg, eng, reqs, prompts,
                                              tiers=tiers)
        s = _score(reqs, planner, gw)
        scores[name] = s
        streams_by_policy[name] = {r: tuple(st.tokens)
                                   for r, st in streams.items()}
        att = s["attainment_by_tier"]
        rows.append((f"gateway/{name}/goodput", wall_s * 1e6,
                     f"{s['goodput_rps']:.1f} ontime req/s virtual "
                     f"({s['completed']} completed, {s['late']} late)"))
        rows.append((f"gateway/{name}/attainment", 0.0,
                     " ".join(f"{t}={att.get(t, 0.0):.3f}"
                              for t in ("interactive", "standard", "batch"))))
        rows.append((f"gateway/{name}/tenant_jain", 0.0,
                     f"{s['tenant_jain']:.4f}"))
        rows.append((f"gateway/{name}/shed_abort", 0.0,
                     f"shed={s['shed']} dropped={s['dropped']} "
                     f"aborted={s['deadline_aborted']}"))
    assert eng.jit_cache_sizes() == jit_before, \
        "measured policy runs recompiled"

    # acceptance: tiers rescue interactive attainment at equal offered
    # load without degrading per-tenant fairness
    fifo, tiered = scores["temporal"], scores["dstack"]
    int_fifo = fifo["attainment_by_tier"].get("interactive", 0.0)
    int_tiered = tiered["attainment_by_tier"].get("interactive", 0.0)
    assert int_tiered > int_fifo, (
        f"tiered admission did not beat FIFO on interactive attainment "
        f"({int_tiered:.3f} vs {int_fifo:.3f})")
    assert tiered["tenant_jain"] >= fifo["tenant_jain"] - 1e-9, (
        f"tiered admission degraded tenant fairness "
        f"({tiered['tenant_jain']:.4f} vs {fifo['tenant_jain']:.4f})")
    rows.append(("gateway/acceptance", 0.0,
                 f"interactive {int_fifo:.3f}->{int_tiered:.3f}, "
                 f"jain {fifo['tenant_jain']:.4f}->"
                 f"{tiered['tenant_jain']:.4f}"))

    # wall-clock pass: same trace, host-paced ticks, the trace ring
    # attached; streams must not move by a bit (deadlines relaxed inside
    # _wall_serve — see its docstring)
    tel = Telemetry(trace=TraceRecorder())
    eng.attach_telemetry(tel)
    try:
        streams, planner, gw, wall_s = _wall_serve(
            cfg, eng, reqs, prompts, tiers=dict(TIER_WEIGHTS),
            telemetry=tel)
    finally:
        eng.attach_telemetry(None)
    got = {r: tuple(st.tokens) for r, st in streams.items()}
    assert got == streams_by_policy["dstack"], \
        "wall-clock serve diverged from virtual-clock serve"
    # host pacing decides how prefills pack, so the wall pass may trace
    # a handful of first-seen packed-prefill shapes; the steady-state
    # decode path must stay frozen and growth must stay O(shapes), not
    # O(requests)
    jit_after = eng.jit_cache_sizes()
    grown = {k: jit_after[k] - jit_before.get(k, 0)
             for k in jit_after if jit_after[k] != jit_before.get(k, 0)}
    assert set(grown) <= {"packed_prefill", "write_segments"}, \
        f"wall-clock pass recompiled the decode path: {grown}"
    assert sum(grown.values()) <= 6, \
        f"wall-clock pass recompilation not shape-bounded: {grown}"
    n_spans = validate_chrome_trace(tel.trace.to_chrome_trace())
    ticks = sum(1 for ev in tel.trace.events if ev["name"] == "dstack.tick")
    assert ticks == gw.server.ticks, "wall-clock pass traced no ticks"
    rows.append(("gateway/wall_clock/bit_exact", wall_s * 1e6,
                 f"{gw.server.ticks} ticks host-paced, streams identical "
                 f"to virtual"))
    rows.append(("gateway/wall_clock/spans", 0.0,
                 f"{n_spans} spans in the trace ring, one dstack.tick per "
                 f"tick"))
    rows.append(("gateway/recompilations", 0.0,
                 f"0 measured; wall pass traced "
                 f"{sum(grown.values())} first-seen prefill packings"))
    return rows, scores


def run_scenarios(quick: bool = True):
    """Seeded scenario census: every generator, deterministic shape."""
    from repro.serving.traffic import (SCENARIOS, TrafficConfig,
                                       make_scenario, offered_by)

    rows = []
    cfg = TrafficConfig(model=MODEL, duration=0.5 if quick else 2.0,
                        rate=120.0, seed=7)
    for name in sorted(SCENARIOS):
        a = make_scenario(name, cfg)
        b = make_scenario(name, cfg)
        assert [(r.arrival, r.rid) for r in a] \
            == [(r.arrival, r.rid) for r in b], f"{name} not deterministic"
        tiers = offered_by(a, "tier")
        rows.append((f"gateway/scenario/{name}", 0.0,
                     f"{len(a)} arrivals "
                     + " ".join(f"{k}={v}"
                                for k, v in sorted(tiers.items()))))
    return rows


def run(quick: bool = True):
    """``benchmarks/run.py`` entry point — CSV rows only."""
    rows, _ = run_with_results(quick)
    return rows + run_scenarios(quick)


def main():
    import argparse

    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="CI-sized pass (default)")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--json", nargs="?", const="BENCH_gateway.json",
                    default=None, metavar="PATH", dest="json_out",
                    help="write rows + per-policy scorecards as "
                         "dstack-bench-v1 JSON (default "
                         "BENCH_gateway.json)")
    args = ap.parse_args()
    quick = not args.full
    rows, scores = run_with_results(quick)
    rows += run_scenarios(quick)
    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}", flush=True)
    if args.json_out:
        payload = _common.bench_payload(
            "bench_gateway", rows,
            args={"quick": quick},
            extra={"scores": scores})
        _common.write_json(args.json_out, payload)
        print(f"wrote {args.json_out} ({len(rows)} rows)")


if __name__ == "__main__":
    main()
