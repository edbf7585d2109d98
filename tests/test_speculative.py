"""Cross-model speculative decoding over the engine pool (ISSUE 9).

The load-bearing claim is DRAFT/VERIFY EQUIVALENCE: greedy speculative
serving emits per-request token streams bit-exact with non-speculative
greedy serving, whatever the draft proposes — acceptance is an arg-max
identity (the verify chunk's logits are computed by the same incremental
chunk-attention contract the decode step obeys), and a rejected draft
rolls the slot back to exactly the state the plain decode path would
hold. Asserted with an identical-weights draft (acceptance 1.0), a
divergent draft (real rejections + rollbacks), under lazy paging with
page pressure, and with knee/EMA gating flipping speculation on and off
mid-stream (the draft-twin desync/re-init path). Plus: page conservation
and canonical free-list order after rollback-heavy serves, a compile
gate (verification rides the pre-warmed chunk/packed lattice — zero new
executables between warm serves), spec counters surfacing through
EngineStats → Prometheus → trace instants, and the pool-plane
``enable_speculation`` wiring including the vocabulary-compatibility
refusal."""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models.registry import build_model
from repro.serving.engine import InferenceEngine, make_engine
from repro.serving.plan import PlannerConfig, StepPlanner, serve_ticks
from repro.serving.request import Request, RequestQueue

CACHE_LEN = 32
N_SLOTS = 4
PAGE = 8
TARGET = "olmo-1b"
DRAFT = "qwen2-0.5b"          # a genuinely smaller dense model; reduced
                              # configs share one clamped vocabulary

INCAPABLE = {
    "ssm": "mamba2-1.3b",         # no KV pages to verify against
    "hybrid": "zamba2-7b",        # per-row conv/ssm state beyond pages+pos
    "encdec": "whisper-small",    # per-row cross-attention K/V
    "moe": "phi3.5-moe-42b-a6.6b",  # capacity dropping is batch-shape dep.
}


def _make_prompt(cfg, rid: int, length: int):
    rng = np.random.default_rng(1000 + rid)
    return {"tokens": jnp.asarray(
        rng.integers(1, cfg.vocab_size, size=(1, length)).astype(np.int32))}


def _workload(cfg, seed: int, n: int, prompt_range=(3, 20),
              budget_range=(2, 10)):
    rng = np.random.default_rng(seed)
    reqs, prompts = [], {}
    for i in range(n):
        p = int(rng.integers(*prompt_range))
        nt = int(rng.integers(*budget_range))
        reqs.append(Request(arrival=0.0, rid=i, model=cfg.name, slo=1e9,
                            n_tokens=nt, prompt_len=p))
        prompts[i] = _make_prompt(cfg, i, p)
    return reqs, prompts


def _serve(cfg, eng, reqs, prompts, **planner_kw):
    eng.release_all_slots()
    eng.reset_stats()
    if eng._draft is not None:
        eng._draft.reset_stats()
    q = RequestQueue(cfg.name, slo=1e9)
    planner = StepPlanner(eng, q, PlannerConfig(gen_len=4, **planner_kw))
    srv = serve_ticks(planner, reqs, lambda r: prompts[r.rid])
    assert not srv.truncated
    return {r: tuple(t) for r, t in planner.streams.items()}, planner, srv


@pytest.fixture(scope="module")
def target():
    """One warm (target, identical-weights draft) pair for the module —
    jit caches persist across tests like the pool's standby engines."""
    cfg = get_config(TARGET).reduced()
    eng = make_engine(cfg, cache_len=CACHE_LEN).init_slots(
        N_SLOTS, paged=True, page_size=PAGE)
    draft = InferenceEngine(eng.api, eng.params,
                            cache_len=CACHE_LEN).init_slots(
        N_SLOTS, paged=False)
    eng.attach_draft(draft, spec_k=3)
    return cfg, eng


@pytest.fixture(scope="module")
def divergent_target():
    """Target paired with a SAME-SHAPE draft whose weights diverge (other
    init seed): drafts are frequently wrong, so every serve exercises
    rejection + rollback."""
    cfg = get_config(TARGET).reduced()
    eng = make_engine(cfg, cache_len=CACHE_LEN).init_slots(
        N_SLOTS, paged=True, page_size=PAGE)
    api = build_model(cfg)
    draft = InferenceEngine(api, api.init(__import__("jax").random.PRNGKey(99)),
                            cache_len=CACHE_LEN).init_slots(
        N_SLOTS, paged=False)
    eng.attach_draft(draft, spec_k=3)
    return cfg, eng


# ---------------------------------------------------------------------------
# draft/verify equivalence: speculative greedy == plain greedy, bit-exact
# ---------------------------------------------------------------------------
def test_speculative_streams_bit_exact(target):
    """Identical-weights draft: every proposal verifies (acceptance 1.0)
    and the streams are the plain-greedy streams, token for token."""
    cfg, eng = target
    reqs, prompts = _workload(cfg, seed=7, n=6)
    base, _, _ = _serve(cfg, eng, reqs, prompts)
    assert base and all(len(t) for t in base.values())
    got, _, _ = _serve(cfg, eng, reqs, prompts, spec_k=3)
    assert got == base
    assert eng.stats.spec_rounds > 0
    assert eng.stats.accepted_tokens == eng.stats.draft_tokens
    assert eng.stats.rollbacks == 0
    # speculation replaced most per-token decode dispatches
    assert eng.stats.decode_steps < sum(len(t) for t in base.values()) / 2


def test_divergent_draft_rolls_back_bit_exact(divergent_target):
    """A frequently-wrong draft: rejections roll back to the exact plain
    decode state, so the streams are STILL bit-exact — speculation can
    cost throughput, never correctness."""
    cfg, eng = divergent_target
    reqs, prompts = _workload(cfg, seed=11, n=6)
    base, _, _ = _serve(cfg, eng, reqs, prompts)
    got, _, _ = _serve(cfg, eng, reqs, prompts, spec_k=3)
    assert got == base
    assert eng.stats.rollbacks > 0, "divergent draft never rejected"
    assert eng.stats.accepted_tokens < eng.stats.draft_tokens


def test_rollback_conserves_pages_and_free_list_canonical(divergent_target):
    """Rejection-heavy serving: every page is conserved (allocator audit)
    and after recovery the free list is back in canonical descending
    order — seeded replays reproduce identical page placement."""
    cfg, eng = divergent_target
    reqs, prompts = _workload(cfg, seed=13, n=8, budget_range=(4, 12))
    _serve(cfg, eng, reqs, prompts, spec_k=3)
    assert eng.stats.rollbacks > 0
    assert eng.check_page_invariants()
    eng.release_all_slots()
    assert eng.free_pages == eng.total_pages
    eng.recover()
    free = eng._kv.allocator._free
    assert free == sorted(free, reverse=True), "free list not canonical"


def test_lazy_page_pressure_degrades_never_preempts(target):
    """Tight lazy pool: speculation degrades k (down to plain decode)
    rather than preempting a resident, and the streams stay bit-exact."""
    cfg, eng_base = target
    reqs, prompts = _workload(cfg, seed=3, n=8, budget_range=(10, 20),
                              prompt_range=(4, 12))
    base, _, _ = _serve(cfg, eng_base, reqs, prompts)
    eng = make_engine(cfg, cache_len=CACHE_LEN).init_slots(
        N_SLOTS, paged=True, page_size=PAGE, total_pages=10)
    draft = InferenceEngine(eng.api, eng.params,
                            cache_len=CACHE_LEN).init_slots(
        N_SLOTS, paged=False)
    eng.attach_draft(draft, spec_k=3)
    got, planner, _ = _serve(cfg, eng, reqs, prompts, spec_k=3, lazy=True)
    assert got == base
    assert eng.check_page_invariants()


def test_gating_desync_and_reinit_bit_exact(target):
    """The roofline-knee gate flips speculation off whenever the decode
    batch is at/over the knee, so slots alternate plain and speculative
    ticks — every plain tick desyncs the draft twin, every later spec
    round re-initializes it from the recorded history. Still bit-exact."""
    cfg, eng = target
    reqs, prompts = _workload(cfg, seed=5, n=6, budget_range=(4, 10))
    base, _, _ = _serve(cfg, eng, reqs, prompts)
    got, _, _ = _serve(cfg, eng, reqs, prompts, spec_k=3, spec_knee_batch=3)
    assert got == base
    assert 0 < eng.stats.spec_rounds
    assert eng.stats.decode_steps > 0      # both modes actually ran


def test_knee_gate_disables_speculation(target):
    """Batch always >= knee -> compute-bound -> never speculate."""
    cfg, eng = target
    reqs, prompts = _workload(cfg, seed=7, n=6)
    base, _, _ = _serve(cfg, eng, reqs, prompts)
    got, _, _ = _serve(cfg, eng, reqs, prompts, spec_k=3, spec_knee_batch=1)
    assert got == base
    assert eng.stats.spec_rounds == 0


def test_acceptance_ema_gate_with_probes(divergent_target):
    """A draft below the acceptance floor disables itself via the trailing
    EMA; periodic probe rounds keep measuring it. Streams bit-exact."""
    cfg, eng = divergent_target
    reqs, prompts = _workload(cfg, seed=17, n=8, budget_range=(6, 14))
    base, _, _ = _serve(cfg, eng, reqs, prompts)
    got, planner, srv = _serve(cfg, eng, reqs, prompts, spec_k=3,
                               spec_min_accept=0.95, spec_probe_every=5)
    assert got == base
    # the gate engaged: fewer spec rounds than eligible decode ticks
    assert eng.stats.spec_rounds < srv.ticks
    assert planner._spec_accept_ema < 1.0


def test_speculation_worthwhile_knee_gate():
    from repro.core.scheduler import speculation_worthwhile
    assert speculation_worthwhile(4, None)          # no knee: CPU tests
    assert speculation_worthwhile(3, 4)             # memory-bound
    assert not speculation_worthwhile(4, 4)         # at the knee
    assert not speculation_worthwhile(9, 4)         # compute-bound


# ---------------------------------------------------------------------------
# compile gate: verification rides pre-warmed executables
# ---------------------------------------------------------------------------
def test_speculative_compile_gate(target):
    """Zero recompiles while serving: a second speculative serve over a
    DIFFERENT workload adds no executables — the draft scan is one traced
    signature and every verify chunk lands on the packed-bucket lattice
    the first serve warmed."""
    cfg, eng = target
    reqs, prompts = _workload(cfg, seed=23, n=6)
    _serve(cfg, eng, reqs, prompts, spec_k=3)       # warm
    warm = dict(eng.jit_cache_sizes())
    assert warm.get("draft_scan", 0) >= 1
    assert warm.get("chunk_prefill", 0) >= 1        # verify path live
    _serve(cfg, eng, reqs, prompts, spec_k=3)       # measured re-serve
    assert eng.jit_cache_sizes() == warm, "speculative serving recompiled"
    # every verify executable sits on the same pow2 lattice the packed
    # machinery buckets to — verification rides it, it does not fork a
    # per-shape executable family of its own
    from repro.serving.engine import _packed_bucket, _pow2_at_least
    for t, r, s in eng._chunk_prefill_jit:
        assert t == _packed_bucket(t) and s == _pow2_at_least(s)
        assert r is None or r == _pow2_at_least(r) or r == eng.slot_len


# ---------------------------------------------------------------------------
# capability boundaries
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("family", sorted(INCAPABLE))
def test_incapable_family_refuses_draft(family):
    cfg = get_config(INCAPABLE[family]).reduced()
    eng = make_engine(cfg, cache_len=CACHE_LEN).init_slots(
        2, paged=bool(build_model(cfg).paged_keys), page_size=PAGE)
    assert not eng.spec_capable()
    draft = InferenceEngine(eng.api, eng.params,
                            cache_len=CACHE_LEN).init_slots(2, paged=False)
    with pytest.raises(ValueError):
        eng.attach_draft(draft, spec_k=3)


def test_vocab_mismatch_refused():
    """Cross-model pairing demands one shared vocabulary — token ids must
    mean the same thing to drafter and verifier."""
    cfg = get_config(TARGET).reduced()
    eng = make_engine(cfg, cache_len=CACHE_LEN).init_slots(
        2, paged=True, page_size=PAGE)
    small = dataclasses.replace(cfg, vocab_size=256)
    api = build_model(small)
    draft = InferenceEngine(api, api.init(__import__("jax").random.PRNGKey(0)),
                            cache_len=CACHE_LEN).init_slots(2, paged=False)
    with pytest.raises(ValueError):
        eng.attach_draft(draft, spec_k=3)


# ---------------------------------------------------------------------------
# observability: counters surface through stats -> Prometheus -> trace
# ---------------------------------------------------------------------------
def test_spec_counters_surface_everywhere(target):
    from repro.serving.telemetry import (MetricsRegistry, Telemetry,
                                         TraceRecorder, export_engine_stats)
    cfg, eng = target
    reqs, prompts = _workload(cfg, seed=31, n=4)
    tel = Telemetry(trace=TraceRecorder())
    eng.attach_telemetry(tel)
    eng._draft.attach_telemetry(tel)
    try:
        _serve(cfg, eng, reqs, prompts, spec_k=3)
    finally:
        eng.attach_telemetry(None)
        eng._draft.attach_telemetry(None)
    kinds = {ev["name"] for ev in tel.trace.events}
    assert {"dstack.engine.spec", "spec_round"} <= kinds
    # the round's two read-backs: the draft's proposals, the verify's
    # per-token argmax
    sites = {ev["args"].get("site") for ev in tel.trace.events
             if ev["name"] == "dstack.engine.host_sync"}
    assert {"spec_proposals", "spec_argmax"} <= sites
    rounds = [ev for ev in tel.trace.events if ev["name"] == "spec_round"]
    assert all("accepted" in ev["args"] and "drafted" in ev["args"]
               for ev in rounds)
    reg = MetricsRegistry()
    export_engine_stats(reg, eng.stats, cfg.name)
    text = reg.render()
    for metric in ("dstack_draft_tokens_total", "dstack_accepted_tokens_total",
                   "dstack_spec_rounds_total", "dstack_spec_rollbacks_total",
                   "dstack_incr_chunks_total"):
        assert metric in text, metric


# ---------------------------------------------------------------------------
# pool plane: cross-model wiring
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_pool_cross_model_speculation():
    """``EnginePool.enable_speculation`` pairs a small hosted model as the
    drafter for a large target; pool serving completes with spec rounds
    on the books and the counters mirrored into PoolResult."""
    from repro.core.simulator import RunRequest
    from repro.serving.pool import build_pool
    pool = build_pool([TARGET, DRAFT], base_slots=2, cache_len=CACHE_LEN,
                      prompt_len=8, page_size=PAGE)
    paired = pool.enable_speculation(TARGET, DRAFT, spec_k=3)
    assert paired >= 1
    for i in range(4):
        pool.push(Request(arrival=0.0, rid=i, model=TARGET, slo=1e9,
                          n_tokens=6, prompt_len=8))
    run = pool.admit(RunRequest(model=TARGET, chips=1, batch=2),
                     now=0.0, gen_len=6)
    assert run is not None
    steps = 0
    while not pool.step_run(run, now=float(steps)) and steps < 64:
        steps += 1
    assert steps < 64
    eng = run.engine
    assert eng.stats.spec_rounds > 0
    assert eng.stats.accepted_tokens <= eng.stats.draft_tokens
    res = pool.snapshot("test", duration=1.0, wall_s=0.0, steps=steps)
    m = res.per_model[TARGET]
    assert m.spec_rounds == eng.stats.spec_rounds
    assert m.draft_tokens == eng.stats.draft_tokens
