"""Bring-up smoke test: the serving path end to end on one TPU v5e chip.

Run from the repository root on a machine with the chip:

    python chip_smoke.py

Every phase runs in this one process (a chip belongs to one process at a
time) and starts no other:

1. device   -- the platform must be ``tpu`` and the chip a v5e, since every
               peak constant in ``repro.core.hardware`` is a v5e constant.
2. kernels  -- each main-path Pallas kernel at qwen2-0.5b, olmo-1b and
               mamba2-1.3b widths in bf16 against its ``repro.kernels.ref``
               oracle (computed at "highest" matmul precision).
3. ticks    -- qwen2-0.5b at its published widths in bf16, served through
               ``StepPlanner``/``serve_ticks`` with paged KV (page_size 16)
               and chunked prefill: the decode and packed-prefill
               executables must contain the Pallas kernels, served
               last-prompt-token logits must match a plain ``api.prefill``,
               and a second pass over the same requests must compile nothing.
4. pool     -- ``build_pool(MODELS_QUICK, reduced=False)`` under the
               ``dstack`` policy: every model serves, nothing recompiles
               after ``warmup()``.

Any failure raises, so the exit code is non-zero; nothing is caught and
passed over. The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Times printed here are bring-up figures for one run, not benchmark results.
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

# max |kernel - oracle| / max |oracle|. Both sides read the same bf16
# inputs; the kernels accumulate in f32 but round their output to bf16
# (relative step 2^-8 = 3.9e-3) and may multiply in bf16 on the MXU. 2e-2
# is five bf16 steps: a wrong mask, page or head shows up as O(1).
KERNEL_TOL = 2e-2
# ||served - plain||_2 / ||plain||_2 over the vocabulary. Both are the same
# bf16 model; they differ in kernels and reduction order (paged chunks and
# segment flash against dense prefill), and each of the 24 layers rounds
# its residual stream to bf16. Independent rounding over 2 x 24 bf16 adds
# grows like sqrt(48) steps of 3.9e-3, about 2.7e-2; 5e-2 leaves room while
# a misplaced page or position gives errors of order 1.
LOGIT_TOL = 5e-2


def log(msg: str) -> None:
    print(msg, flush=True)


def device_info() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_v5e(info: dict) -> None:
    if info["platform"] != "tpu":
        raise SystemExit(f"chip_smoke: no TPU found (JAX platform "
                         f"{info['platform']!r}); this script runs on a "
                         f"TPU v5e only")
    from repro.core.hardware import V5E
    if info["kind"] not in V5E.device_kinds:
        raise SystemExit(f"chip_smoke: device {info['kind']!r} is not a "
                         f"TPU v5e; the peak constants in "
                         f"repro.core.hardware describe only a v5e")


class CompileLog:
    """Counts backend compiles (persistent-cache loads included) and sums
    their seconds, from JAX's own monitoring events, from construction on."""
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.n, self.secs = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self)

    def __call__(self, event, duration, **_):
        if event == self.EVENT:
            self.n += 1
            self.secs += duration


def check(ok, what) -> None:
    """A failed check ends the run (kept under ``python -O``)."""
    if not ok:
        raise AssertionError(what)


def _rel_max(out, want) -> float:
    import numpy as np
    out = np.asarray(out, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.abs(out - want).max() / np.abs(want).max())


# --------------------------------------------------------------- kernels
def check_kernels(attn_cfgs, ssd_cfg, *, interpret=False, seed=0):
    """Each main-path kernel against its oracle, at the head counts and
    widths of ``attn_cfgs`` (attention) and ``ssd_cfg`` (SSD scan);
    returns {name: max relative error} and raises above ``KERNEL_TOL``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops, ref
    from repro.kernels.chunk_attention import paged_chunk_attention
    from repro.kernels.decode_attention import decode_attention
    from repro.kernels.flash_attention import (flash_attention,
                                               segment_flash_attention)
    from repro.kernels.paged_attention import paged_decode_attention
    from repro.models.layers import gather_pages

    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 64))
    rng = np.random.default_rng(seed)
    bf16 = jnp.bfloat16

    def rnd(*shape):
        return jax.random.normal(next(keys), shape).astype(bf16)

    def oracle(fn, *args, **kw):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kw)

    def tables(rows, max_pages):
        # pages scattered through the pool; page 0 (the null page) unused
        ids = rng.permutation(np.arange(1, rows * max_pages + 1))
        return jnp.asarray(ids.reshape(rows, max_pages), jnp.int32)

    errs = {}
    ps = 16
    for cfg in attn_cfgs:
        name, h, kv, d = (cfg.name, cfg.num_heads, cfg.num_kv_heads,
                          cfg.resolved_head_dim)
        # paged decode: 4 rows, 512-token tables, ragged lengths
        b, mp = 4, 32
        q, kp, vp = rnd(b, h, d), rnd(kv, b * mp + 1, ps, d), \
            rnd(kv, b * mp + 1, ps, d)
        tbl = tables(b, mp)
        lens = jnp.asarray([1, 100, 257, mp * ps], jnp.int32)
        out = paged_decode_attention(q, kp, vp, tbl, lens,
                                     interpret=interpret)
        want = oracle(ref.decode_attention_ref, q, gather_pages(kp, tbl),
                      gather_pages(vp, tbl), lens)
        errs[f"paged_decode[{name}]"] = _rel_max(out, want)

        # ragged decode over contiguous caches
        kc, vc = rnd(b, 512, kv, d), rnd(b, 512, kv, d)
        out = decode_attention(q, kc, vc, lens, interpret=interpret)
        want = oracle(ref.decode_attention_ref, q, kc, vc, lens)
        errs[f"ragged_decode[{name}]"] = _rel_max(out, want)

        # incremental chunk: 2 segments of up to 64 new rows on history
        s, r, mp = 2, 64, 16
        qc, kcc, vcc = rnd(s, r, h, d), rnd(s, r, kv, d), rnd(s, r, kv, d)
        kp, vp = rnd(kv, s * mp + 1, ps, d), rnd(kv, s * mp + 1, ps, d)
        tbl = tables(s, mp)
        hist = jnp.asarray([100, 37], jnp.int32)
        slen = jnp.asarray([64, 20], jnp.int32)
        out = paged_chunk_attention(qc, kp, vp, kcc, vcc, tbl, hist, slen,
                                    interpret=interpret)
        want = oracle(ref.chunk_attention_ref, qc, gather_pages(kp, tbl),
                      gather_pages(vp, tbl), kcc, vcc, hist)
        errs[f"chunk[{name}]"] = max(
            _rel_max(np.asarray(out, np.float32)[i, :n],
                     np.asarray(want, np.float32)[i, :n])
            for i, n in enumerate(np.asarray(slen)))

        # causal flash (whole-prompt prefill)
        t = 512
        qf, kf, vf = rnd(1, t, h, d), rnd(1, t, kv, d), rnd(1, t, kv, d)
        out = flash_attention(qf, kf, vf, causal=True, interpret=interpret)
        want = oracle(ref.attention_ref, qf, kf, vf, causal=True)
        errs[f"flash[{name}]"] = _rel_max(out, want)

        # segment flash (packed prefill): 4 segments in a 1024-token row
        t = 1024
        qf, kf, vf = rnd(1, t, h, d), rnd(1, t, kv, d), rnd(1, t, kv, d)
        seg = jnp.asarray(np.repeat([0, 1, 2, 3], [300, 500, 150, 74]),
                          jnp.int32)
        out = segment_flash_attention(qf, kf, vf, seg, interpret=interpret)
        want = oracle(ref.packed_attention_ref, qf, kf, vf, seg)
        errs[f"segment_flash[{name}]"] = _rel_max(out, want)

    # SSD scan through ops.ssd: a length that is not a chunk multiple
    # (the padding path) and a carried-in state
    name, heads, p, n = (ssd_cfg.name, ssd_cfg.ssm_heads,
                         ssd_cfg.ssm_head_dim, ssd_cfg.ssm_state)
    b, l = 2, 300
    x = rnd(b, l, heads, p)
    dt = jax.nn.softplus(jax.random.normal(next(keys), (b, l, heads)))
    a = -jnp.exp(0.5 * jax.random.normal(next(keys), (heads,)))
    bb, cc = rnd(b, l, n), rnd(b, l, n)
    s0 = 0.1 * jax.random.normal(next(keys), (b, heads, n, p))
    y, st = ops.ssd(x, dt, a, bb, cc, chunk=ssd_cfg.ssm_chunk,
                    initial_state=s0,
                    backend="interpret" if interpret else "pallas")
    y_ref, st_ref = oracle(ref.ssd_ref, x, dt, a, bb, cc, initial_state=s0)
    errs[f"ssd[{name}]"] = max(_rel_max(y, y_ref), _rel_max(st, st_ref))

    for k, v in errs.items():
        log(f"kernels: {k} max_rel_err={v:.3e}")
    bad = {k: v for k, v in errs.items() if not v <= KERNEL_TOL}
    check(not bad, f"kernels above tolerance {KERNEL_TOL}: {bad}")
    return errs


# ------------------------------------------------------------ tick plane
def serve_tick_plane(cfg, *, n_requests=8, prompt_lens=(100, 1200),
                     gen_tokens=32, page_size=16, chunk_tokens=512,
                     seed=0, compile_log=None):
    """Serve ``n_requests`` seeded random prompts through the tick plane
    twice (warm-up, then measured) and check what the measured pass ran:
    every request served, no executable added, logits within
    ``LOGIT_TOL``. Returns counts, seconds, and which executables hold a
    Pallas kernel."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.serving.engine import make_engine
    from repro.serving.plan import PlannerConfig, StepPlanner, serve_ticks
    from repro.serving.request import Request, RequestQueue

    compile_log = compile_log or CompileLog()
    rng = np.random.default_rng(seed)
    lens = rng.integers(prompt_lens[0], prompt_lens[1] + 1, n_requests)
    prompts = {i: rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for i, n in enumerate(lens)}
    reqs = [Request(arrival=0.0, rid=i, model=cfg.name, slo=1e9,
                    n_tokens=gen_tokens, prompt_len=int(n))
            for i, n in enumerate(lens)]
    slot_len = -(-(int(lens.max()) + gen_tokens) // 128) * 128

    t0 = time.perf_counter()
    eng = make_engine(cfg, seed=seed, cache_len=slot_len).init_slots(
        n_requests, paged=True, page_size=page_size)
    jax.block_until_ready(eng.params)
    setup_s = time.perf_counter() - t0

    def serve():
        eng.release_all_slots()
        eng.reset_stats()
        planner = StepPlanner(eng, RequestQueue(cfg.name, slo=1e9),
                              PlannerConfig(chunk_tokens=chunk_tokens,
                                            gen_len=gen_tokens))
        srv = serve_ticks(planner, reqs, lambda r: {
            "tokens": jnp.asarray(prompts[r.rid])[None]})
        check(not srv.truncated, "tick plane hit its event backstop")
        return planner, srv

    c0, s0 = compile_log.n, compile_log.secs
    t0 = time.perf_counter()
    serve()
    warm_s = time.perf_counter() - t0
    warm_compiles, warm_compile_s = compile_log.n - c0, compile_log.secs - s0

    # measured pass: record each prefill dispatch's packed input, row
    # length and per-segment logits (device arrays, read after the pass)
    calls = []
    packed_prefill, chunk_prefill = eng.prefill_packed, eng.prefill_chunk_packed

    def spy_packed(packed, row_len):
        out = packed_prefill(packed, row_len)
        calls.append(("packed", packed, row_len, out[0]))
        return out

    def spy_chunk(packed, row_len):
        out = chunk_prefill(packed, row_len)
        calls.append(("chunk", packed, row_len, out[0]))
        return out

    eng.prefill_packed, eng.prefill_chunk_packed = spy_packed, spy_chunk
    jit0, c0 = eng.jit_cache_sizes(), compile_log.n
    t0 = time.perf_counter()
    planner, srv = serve()
    serve_s = time.perf_counter() - t0
    compiles_after_warmup = compile_log.n - c0
    del eng.prefill_packed, eng.prefill_chunk_packed
    check(eng.jit_cache_sizes() == jit0, "the measured pass recompiled")

    done = {rid: len(toks) for rid, toks in planner.streams.items()}
    check(sorted(done) == list(range(n_requests)) and all(
        n == gen_tokens for n in done.values()), f"streams: {done}")
    stats = eng.stats

    # last-prompt-token logits of each request: the segment whose tokens
    # end its prompt
    served = {}
    for kind, packed, _, logits in calls:
        toks = np.asarray(packed["tokens"])[0]
        starts = np.asarray(packed["seg_starts"])
        seg_lens = np.asarray(packed["seg_lens"])
        hists = (np.asarray(packed["hist_lens"]) if kind == "chunk"
                 else np.zeros_like(seg_lens))
        for i, (st, n, h) in enumerate(zip(starts, seg_lens, hists)):
            for rid, p in prompts.items():
                if n and h + n == len(p) and np.array_equal(
                        p[h:], toks[st:st + n]):
                    served[rid] = np.asarray(logits[i], np.float32)
    check(sorted(served) == list(range(n_requests)),
          f"final-chunk logits found for {sorted(served)}")
    prefill = jax.jit(eng.api.prefill, static_argnums=2)
    rel = {}
    for rid, p in prompts.items():
        want, _ = prefill(eng.params, {"tokens": jnp.asarray(p)[None]},
                          len(p))
        want = np.asarray(want[0], np.float32)[:cfg.vocab_size]
        got = served[rid][:cfg.vocab_size]
        rel[rid] = float(np.linalg.norm(got - want) / np.linalg.norm(want))

    # which executables hold a Pallas kernel (tpu_custom_call)
    def has_kernel(fn, *args):
        return "tpu_custom_call" in fn.lower(*args).compile().as_text()

    kernels = {"decode": has_kernel(
        eng._get_slot_step(None), eng.params, eng._last_tok,
        eng._slot_cache, jnp.zeros((eng.n_slots,), bool))}

    def key(pk, row_len):
        return pk["tokens"].shape[1], row_len, pk["seg_starts"].shape[0]

    big = [(pk, r) for kind, pk, r, _ in calls
           if kind == "packed" and pk["tokens"].shape[1] >= 512]
    check(big, "no packed prefill reached 512 tokens")
    pk, r = big[0]
    kernels["packed_prefill"] = has_kernel(
        eng._packed_prefill_jit[key(pk, r)], eng.params, pk)
    chunks = [(pk, r) for kind, pk, r, _ in calls if kind == "chunk"]
    check(chunks, "no incremental chunk prefill ran")
    pk, r = chunks[0]
    kernels["chunk_prefill"] = has_kernel(
        eng._chunk_prefill_jit[key(pk, r)], eng.params, pk, eng._slot_cache)

    out = {
        "requests": n_requests, "prompt_tokens": int(lens.sum()),
        "tokens_out": sum(done.values()), "ticks": srv.ticks,
        "dispatches": srv.dispatches,
        "packed_prefills": stats.packed_prefills,
        "chunk_prefills": stats.chunk_prefills,
        "max_packed_tokens": max(pk["tokens"].shape[1]
                                 for _, pk, _, _ in calls),
        "setup_s": setup_s, "warm_s": warm_s,
        "warm_compiles": warm_compiles, "warm_compile_s": warm_compile_s,
        "serve_s": serve_s, "compiles_after_warmup": compiles_after_warmup,
        "logit_rel_err_max": max(rel.values()),
        "kernels": kernels,
    }
    for k, v in out.items():
        log(f"ticks: {k}={v}")
    check(out["logit_rel_err_max"] <= LOGIT_TOL,
          f"served logits off by {rel} > {LOGIT_TOL}")
    return out


# ------------------------------------------------------------------ pool
def run_pool(models, *, reduced=False, allocations=None, rate=2000.0,
             duration=0.05, compile_log=None):
    """``build_pool`` + ``run_policy(pool, "dstack")``: every model must
    serve and nothing may compile after ``warmup()``."""
    from repro.serving.controller import run_policy
    from repro.serving.pool import build_pool

    compile_log = compile_log or CompileLog()
    c0, s0 = compile_log.n, compile_log.secs
    t0 = time.perf_counter()
    pool = build_pool(models, request_rate=rate, reduced=reduced,
                      allocations=allocations)
    build_s = time.perf_counter() - t0
    engines = sum(len(h.allocations) for h in pool.hosts.values())
    warm_compiles, warm_compile_s = compile_log.n - c0, compile_log.secs - s0
    jit0, c0 = pool.jit_cache_sizes(), compile_log.n
    t0 = time.perf_counter()
    res = run_policy(pool, "dstack", rate=rate, duration=duration)
    serve_s = time.perf_counter() - t0
    compiles_after_warmup = compile_log.n - c0
    check(not res.truncated, "dstack run hit a controller backstop")
    check(pool.jit_cache_sizes() == jit0, "serving recompiled")
    served = {n: m.completed for n, m in sorted(res.per_model.items())}
    out = {"models": len(models), "standby_engines": engines,
           "allocations": "given" if allocations else "default",
           "build_warm_s": build_s, "warm_compiles": warm_compiles,
           "warm_compile_s": warm_compile_s, "serve_s": serve_s,
           "decode_dispatches": res.steps, "served": served,
           "compiles_after_warmup": compiles_after_warmup}
    for k, v in out.items():
        log(f"pool: {k}={v}")
    check(len(served) == len(models) and min(served.values()) >= 1,
          f"served per model: {served}")
    return out


def main() -> None:
    info = device_info()
    require_v5e(info)
    log(f"device: platform={info['platform']} kind={info['kind']} "
        f"count={info['count']}")

    from benchmarks.bench_pool import MODELS_QUICK
    from repro.configs import get_config
    from repro.utils.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    compile_log = CompileLog()

    t0 = time.perf_counter()
    check_kernels([get_config("qwen2-0.5b"), get_config("olmo-1b")],
                  get_config("mamba2-1.3b"))
    log(f"kernels: wall_s={time.perf_counter() - t0:.3f}")
    ticks = serve_tick_plane(get_config("qwen2-0.5b"),
                             compile_log=compile_log)
    check(all(ticks["kernels"].values()),
          f"an executable holds no Pallas kernel: {ticks['kernels']}")
    run_pool(MODELS_QUICK, compile_log=compile_log)
    log(f"compiles: n={compile_log.n} seconds={compile_log.secs:.3f}")
    print(json.dumps({"ok": True, "device": info}), flush=True)


if __name__ == "__main__":
    main()
