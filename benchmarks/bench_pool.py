"""Control-plane benchmark: every policy family over REAL jitted engines.

The apples-to-apples comparison the paper runs against Clipper/Nexus-style
baselines (§7): the same deterministic workload is served through the same
``EnginePool`` of slot-based engines by each policy family —

  temporal         pure time-sharing, full pod per run (Clipper/Nexus)
  fixed_batch_mps  uncontrolled spatial sharing (default MPS)
  maxmin / gslice  fair / static spatial partitioning
  dstack           the paper's dynamic fair spatio-temporal scheduler

Engines are compiled ONCE up front (one standby executable per candidate
allocation) and reused across all policies; the measured runs compile
nothing. Virtual time comes from the profile rooflines at each run's
granted allocation (deterministic, SLO-meaningful); every decode step is
still a real dispatch, and wall_s is the host time that took.

CLI: ``PYTHONPATH=src python benchmarks/bench_pool.py [--quick|--full]
[--faults]``; also wired into ``benchmarks/run.py`` as ``bench_pool``.
``--faults`` appends the chaos pass: a seeded ``FaultInjector`` (attached
AFTER warmup) drives transient dispatch faults, injected allocator
failures, and engine resets through a lazy pool serve, asserting the
ISSUE 6 acceptance bar end to end — the pool drains, pages conserve
(``check_page_invariants``), per-cause counters surface in the result,
and recovery compiles NOTHING.
"""
from __future__ import annotations

import time

try:                      # package context (benchmarks/run.py)
    from benchmarks import common as _common
except ImportError:       # script context (python benchmarks/bench_pool.py)
    import common as _common

MODELS_QUICK = ["qwen2-0.5b", "olmo-1b", "mamba2-1.3b"]
MODELS_FULL = MODELS_QUICK + ["whisper-small"]
POLICIES_QUICK = ["temporal", "fixed_batch_mps", "maxmin", "dstack"]
POLICIES_FULL = ["temporal", "fixed_batch_mps", "gslice", "triton",
                 "maxmin", "max_throughput", "dstack"]


def run(quick: bool = True):
    """``benchmarks/run.py`` entry point — CSV rows only."""
    rows, _ = run_with_results(quick)
    return rows


def run_faults(quick: bool = True):
    """The chaos pass (``--faults``): serve a lazy tight-page pool under
    a seeded fault schedule and assert the fault-tolerance acceptance
    invariants. Returns CSV rows like every other bench."""
    from repro.serving.controller import run_policy
    from repro.serving.faults import FaultInjector
    from repro.serving.pool import build_pool

    rate = 2000.0
    duration = 0.05 if quick else 0.25
    t0 = time.time()
    pool = build_pool(["olmo-1b"], request_rate=rate, base_slots=4,
                      cache_len=32, pages={"olmo-1b": 8}, lazy_kv=True)
    jit_before = pool.jit_cache_sizes()
    # attached AFTER warmup: the fault schedule must not depend on (or
    # perturb) compilation order, and recovery must reuse warm executables
    inj = FaultInjector(seed=17, dispatch_rate=0.05, alloc_rate=0.05,
                        max_faults=24)
    engines = [a.engine for h in pool.hosts.values()
               for a in h.allocations.values()]
    for eng in engines:
        eng.attach_faults(inj, max_retries=1)
    try:
        # drain mode: the acceptance bar is that a seeded chaos run
        # DRAINS — every request reaches a terminal state and every page
        # returns (a duration-cutoff run would leave legitimate
        # residents holding pages)
        res = run_policy(pool, "dstack", rate=rate, duration=duration,
                         gen_len=4, gen_tokens=(4, 20), drain=True)
    finally:
        for eng in engines:
            eng.attach_faults(None, max_retries=2)
    assert not res.truncated, "chaos run hit a controller backstop"
    m = res.per_model["olmo-1b"]
    rows = [("pool/faults/injected", (time.time() - t0) * 1e6,
             f"dispatch={inj.injected['dispatch']} "
             f"alloc={inj.injected['alloc']} "
             f"retries={m.engine_retries} resets={m.engine_resets}"),
            ("pool/faults/served", 0.0,
             f"served={m.completed} preempt={m.preemptions} "
             f"requeue={m.requeues} viol={m.violated}")]
    # the acceptance bar: chaos actually ran, the pool still served, no
    # page leaked, and recovery compiled nothing
    assert inj.total > 0, "fault schedule never fired"
    assert m.engine_retries > 0, "no transient fault was retried"
    assert m.completed > 0, "faulted pool served nothing"
    for eng in engines:
        assert eng.free_pages == eng.total_pages, "faulted pool leaked pages"
        eng.check_page_invariants()
    assert pool.jit_cache_sizes() == jit_before, "fault recovery recompiled"
    rows.append(("pool/faults/recompilations", 0.0, "0"))
    rows.append(("pool/faults/page_leaks", 0.0, "0"))
    return rows


def run_with_results(quick: bool = True):
    from repro.serving.controller import run_policy
    from repro.serving.pool import build_pool

    models = MODELS_QUICK if quick else MODELS_FULL
    policies = POLICIES_QUICK if quick else POLICIES_FULL
    rate = 2000.0
    duration = 0.05 if quick else 0.25
    gen_len = 4

    t0 = time.time()
    pool = build_pool(models, request_rate=rate, base_slots=4, cache_len=32)
    rows = [("pool/build_warm_s", (time.time() - t0) * 1e6,
             f"{len(models)} models, "
             f"{sum(len(h.allocations) for h in pool.hosts.values())} "
             f"standby engines")]
    jit_before = pool.jit_cache_sizes()

    results = []
    for pol in policies:
        res = run_policy(pool, pol, rate=rate, duration=duration,
                         gen_len=gen_len)
        assert not res.truncated, f"{pol} hit a controller backstop"
        results.append(res)
        rows.append((f"pool/{pol}/throughput", res.wall_s * 1e6,
                     f"{res.throughput():.1f} req/s virtual "
                     f"({res.total_completed} served)"))
        rows.append((f"pool/{pol}/violations", 0.0,
                     f"{res.total_violated}"))
        rows.append((f"pool/{pol}/jain_fairness", 0.0,
                     f"{res.fairness():.3f}"))
        rows.append((f"pool/{pol}/occupancy", 0.0, f"{res.occupancy:.3f}"))
        for n, m in sorted(res.per_model.items()):
            rows.append((f"pool/{pol}/{n.split('-')[0]}", 0.0,
                         f"served={m.completed} viol={m.violated} "
                         f"p50={m.p50 * 1e3:.2f}ms p99={m.p99 * 1e3:.2f}ms"
                         + (f" ttft_p50={m.ttft_p50 * 1e3:.2f}ms"
                            if m.ttfts else "")))

    # the acceptance invariant: standby executables were compiled up front;
    # serving every policy family recompiled NOTHING
    jit_after = pool.jit_cache_sizes()
    rows.append(("pool/recompilations", 0.0,
                 "0" if jit_after == jit_before else
                 f"CHANGED: {jit_before} -> {jit_after}"))
    assert jit_after == jit_before, "serving recompiled an executable"

    # lazy-KV pool: admission reserves prompt-only pages on a tight page
    # budget, decode grows page-by-page, and OutOfPages mid-run preempts
    # the newest resident and requeues its request — end to end through
    # the Controller, still with 0 recompiles (growth executables are
    # warmed up front like everything else)
    t0 = time.time()
    lazy = build_pool(["olmo-1b"], request_rate=rate, base_slots=4,
                      cache_len=32, pages={"olmo-1b": 8}, lazy_kv=True)
    jb = lazy.jit_cache_sizes()
    res = run_policy(lazy, "dstack", rate=rate, duration=duration,
                     gen_len=4, gen_tokens=(4, 20))
    m = res.per_model["olmo-1b"]
    rows.append(("pool/lazy_kv/preemptions", (time.time() - t0) * 1e6,
                 f"preempt={m.preemptions} requeue={m.requeues} "
                 f"served={m.completed} topups={m.topups} "
                 f"(8-page pool, ragged budgets 4..20)"))
    assert m.preemptions > 0 and m.requeues > 0, \
        "lazy pool never exercised preempt-and-requeue"
    assert lazy.jit_cache_sizes() == jb, "lazy serving recompiled"

    # radix prompt cache pool: pool prompts share their template by
    # construction, so every admission after the first can alias the
    # cached prefix pages. The gate here is the serving discipline —
    # hits, COW copies, and teacher-forced catch-up all ride executables
    # warmed up front (warm_prefix_ops), 0 recompiles; the token-savings
    # and bit-exactness bars live in bench_decode --shared-prefix
    t0 = time.time()
    pfx = build_pool(["olmo-1b"], request_rate=rate, base_slots=4,
                     cache_len=64, prompt_len=24, prefix_cache=True)
    jb = pfx.jit_cache_sizes()
    res = run_policy(pfx, "dstack", rate=rate, duration=duration,
                     gen_len=4)
    m = res.per_model["olmo-1b"]
    rows.append(("pool/prefix_cache/hits", (time.time() - t0) * 1e6,
                 f"hits={m.prefix_hits} aliased={m.prefix_hit_tokens}tok "
                 f"cow={m.cow_copies} served={m.completed}"))
    assert m.prefix_hits > 0, "prefix-cache pool never hit"
    assert pfx.jit_cache_sizes() == jb, "prefix-cache serving recompiled"
    return rows, results


def run_telemetry(quick: bool = True, trace_path=None):
    """The telemetry pass (always on under main()): serve the dstack
    policy with the ``Telemetry`` plane attached, its ``TraceRecorder``
    ring recording the engines' spans and the requests' lifecycle
    instants. The Chrome trace is validated, and written to
    ``trace_path`` when set. Attaching telemetry must neither recompile
    nor change behavior (asserted here via jit_cache_sizes; bit-identity
    is proved in tests/test_telemetry.py). Returns (rows, Prometheus
    text, PoolResult)."""
    from repro.serving.controller import run_policy
    from repro.serving.pool import build_pool
    from repro.serving.telemetry import (MetricsRegistry, Telemetry,
                                         TraceRecorder, export_pool_result,
                                         validate_chrome_trace)

    rate = 2000.0
    duration = 0.05 if quick else 0.25
    t0 = time.time()
    pool = build_pool(["qwen2-0.5b", "olmo-1b"], request_rate=rate,
                      base_slots=4, cache_len=32)
    jit_before = pool.jit_cache_sizes()
    # attached AFTER warmup (like faults): the ring sees warm serving
    tel = Telemetry(trace=TraceRecorder())
    pool.attach_telemetry(tel)
    try:
        res = run_policy(pool, "dstack", rate=rate, duration=duration,
                         gen_len=4, gen_tokens=(4, 12))
    finally:
        pool.attach_telemetry(None)
    assert not res.truncated, "telemetry pass hit a controller backstop"
    assert pool.jit_cache_sizes() == jit_before, "telemetry recompiled"
    obj = tel.trace.to_chrome_trace()
    n_spans = validate_chrome_trace(obj)
    dispatches = sum(1 for ev in tel.trace.events
                     if ev.get("cat") == "dispatch")
    assert dispatches, "telemetry pass traced no dispatches"
    rows = [("pool/telemetry/dispatch_spans", (time.time() - t0) * 1e6,
             f"{dispatches} dispatch spans over "
             f"{len(tel.trace.tracks())} tracks")]
    # per-request streaming latency (TTFT/TBT), virtual time — the
    # figures end-to-end latency hides (satellite: RequestQueue TTFT)
    for n, m in sorted(res.per_model.items()):
        if m.ttfts:
            rows.append((f"pool/telemetry/{n.split('-')[0]}_ttft_p50",
                         m.ttft_p50 * 1e6,
                         f"p99={m.ttft_p99 * 1e6:.0f}us virtual "
                         f"(n={len(m.ttfts)}, "
                         f"tbt_p50={m.tbt_p50 * 1e6:.1f}us)"))
    reg = MetricsRegistry()
    export_pool_result(reg, res)
    prom = reg.render()
    if trace_path:
        tel.trace.save(trace_path)
        rows.append(("pool/telemetry/trace", 0.0,
                     f"{len(obj['traceEvents'])} events ({n_spans} spans, "
                     f"{len(tel.trace.tracks())} tracks) -> {trace_path}"))
    return rows, prom, res


def main():
    import argparse

    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="CI-sized pass: 3 models, 4 policy families")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--faults", action="store_true",
                    help="append the seeded chaos pass (fault injection "
                         "through a lazy pool; asserts the ISSUE 6 "
                         "acceptance invariants)")
    ap.add_argument("--trace", nargs="?", const="trace_pool.json",
                    default=None, metavar="PATH",
                    help="write a Perfetto-loadable Chrome trace of the "
                         "telemetry pass to PATH (default "
                         "trace_pool.json)")
    ap.add_argument("--json", nargs="?", const="BENCH_pool.json",
                    default=None, metavar="PATH", dest="json_out",
                    help="write rows + Prometheus snapshot as "
                         "dstack-bench-v1 JSON (default BENCH_pool.json)")
    args = ap.parse_args()
    quick = not args.full
    rows, results = run_with_results(quick)
    if args.faults:
        rows += run_faults(quick)
    trows, prom, _ = run_telemetry(quick, trace_path=args.trace)
    rows += trows
    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}", flush=True)
    print()
    print("policy           summary (virtual time; real jitted engines)")
    for res in results:
        for line in res.table_rows():
            print(line)
    if args.json_out:
        payload = _common.bench_payload(
            "bench_pool", rows,
            args={"quick": quick, "faults": bool(args.faults),
                  "trace": bool(args.trace)},
            extra={"prometheus": prom})
        _common.write_json(args.json_out, payload)
        print(f"wrote {args.json_out} ({len(rows)} rows)")


if __name__ == "__main__":
    main()
