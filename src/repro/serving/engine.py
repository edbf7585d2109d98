"""Batched inference engine — the data plane under the D-STACK scheduler.

One engine instance wraps one (model, sub-mesh) pair: jitted prefill and
decode executables, a KV/state cache, and greedy generation. On a real pod
the scheduler holds one engine per (model, chip-allocation) — this is the
TPU analogue of the paper's CUDA-MPS process with a fixed GPU% (§3.2): the
compiled executable pins the spatial allocation, and re-allocation means
switching to a standby engine compiled for a different sub-mesh while the
active one keeps serving.

Decode hot-path architecture
----------------------------
The paper's throughput gains assume the data plane keeps the accelerator
saturated while the scheduler multiplexes models; three mechanisms here
make that true on the host side:

1. **Scan-based generation.** ``generate`` runs the whole autoregressive
   loop as a single jitted ``jax.lax.scan`` with the KV cache donated into
   the executable — ONE dispatch per generate call instead of one per
   token. The eager per-token loop survives as ``generate_eager`` (it is
   the benchmark baseline; see ``benchmarks/bench_decode.py``).

2. **Power-of-two bucketing.** Executables specialize on cache shape AND
   scan length, so naively sizing the cache to ``prompt +
   max_new_tokens`` (or the scan to the exact token count) re-compiles
   for every distinct request. ``bucket_len`` rounds the cache length up
   to the next power of two (floored at the engine's base ``cache_len``)
   and ``generate`` buckets the scan length the same way (surplus tokens
   discarded): prefill/decode/generate executables are compiled once per
   bucket — O(log max_len) compilations total — and reused for every
   request that fits.

3. **Packed ragged prefill for admission bursts.** ``insert_many``
   admits a WHOLE admission batch in one prefill dispatch: the prompts
   are concatenated into a single (1, total_tokens) row with per-token
   segment ids (bucketed to a power of two — O(log max_len)
   executables), run through the family's ``prefill_packed`` (segment-
   masked attention; SSM state resets at segment boundaries), and each
   segment's K/V is scattered DIRECTLY into its slot's pages by one
   jitted token-indexed scatter — no per-request dispatches, no
   pad-to-max FLOPs, no intermediate dense per-slot copy. The pool's
   admission and topup paths batch through it.

4. **Slot-based continuous batching over a PAGED KV cache.**
   ``init_slots`` allocates a fixed number of slots whose K/V storage is,
   by default, a shared pool of fixed-size pages indexed per sequence by a
   block table (``repro.serving.kv_cache``; ``paged=False`` restores the
   original per-slot ring, kept as the parity/bench baseline). ``insert``
   prefills one request, allocates pages for its prompt plus its decode
   budget (``n_tokens``), and scatters the prompt K/V into them;
   ``step`` decodes one token for all slots in a single dispatch and
   reports which slots just exhausted their budget (per-request ragged
   generation lengths — the done flags drive early slot free and mid-run
   re-admission upstream); ``free`` returns the slot's pages to the pool
   and parks its table row on the null page. Because every sequence
   carries its own position/length (``cache["pos"]`` is a (B,) vector end
   to end) and pages are fully indirect, admitting a new request never
   repads, recompiles, moves another sequence's cache, or perturbs other
   slots — and KV memory in use tracks tokens actually resident instead
   of n_slots × max_len (the admission bottleneck paging removes).

5. **Declarative step plans.** ``execute(plan)`` is the single entry
   point the serving control planes drive: one ``StepPlan``
   (``repro.serving.plan``) per tick runs frees → preemptions → lazy
   page grows → first prefill chunks (ONE packed prefill) → chunk
   continuations (ONE packed prefix-recompute prefill over every
   mid-prefill slot, riding the same executables and segment scatter as
   admissions) → decodes (ONE masked slot step) — at most three model
   dispatches per tick, all against pre-compiled executables. Prefix
   recompute plus the PR-4 packed-parity guarantee is what makes
   chunked prefill bit-exact with one-shot prefill; lazy reservation +
   ``grow_slot`` is what makes vLLM-style preempt-and-requeue a plan
   variant instead of a new code path.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import layers as L
from repro.models.registry import ModelAPI
from repro.serving.faults import EngineFault, TransientFault
from repro.serving.kv_cache import NULL_PAGE, OutOfPages, PagedKVCache
from repro.serving.telemetry import Telemetry, span


def _pow2_at_least(n: int) -> int:
    return 1 << max(0, int(n) - 1).bit_length()


def _packed_bucket(n: int) -> int:
    """Packed-token bucket: smallest of {2^k, 3·2^(k-1)} >= n. The packed
    prefill row is the SUM of an admission batch's prompt lengths, so its
    padding waste is pure lost prefill throughput; the half-step doubles
    the executable count per octave (still O(log max_len)) and caps the
    waste at 33% instead of 100%."""
    p = _pow2_at_least(n)
    half = 3 * p // 4
    return half if half >= n else p


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Static sampling configuration — hashable, so it is part of the jitted
    generate executable's cache key (one executable per distinct setting,
    reused across requests). temperature <= 0 means greedy arg-max."""
    temperature: float = 1.0
    top_k: int = 0
    top_p: float = 1.0


@dataclasses.dataclass
class EngineStats:
    prefills: int = 0          # prefill DISPATCHES (a packed one counts 1)
    packed_prefills: int = 0   # of which packed multi-segment dispatches
    # chunk-continuation DISPATCHES: one packed prefix-recompute prefill
    # advances every mid-prefill slot's chunk (StepPlan admissions with
    # start > 0). Each is also counted in prefills/packed_prefills (it
    # IS a packed prefill), and prefill_tokens charges the full prefix
    # rows it computed — recompute waste stays visible
    chunk_prefills: int = 0
    # prompt tokens prefilled: what the dispatch actually computed — the
    # packed path charges sum(real lens), `prefill` charges B×S as given
    # (includes padding only if the CALLER padded the batch)
    prefill_tokens: int = 0
    decode_steps: int = 0
    tokens_out: int = 0
    inserts: int = 0
    # lazy page growth: block-table extension dispatches (page
    # boundaries crossed under PlannerConfig.lazy)
    grows: int = 0
    # fault tolerance (ISSUE 6): transient dispatch faults absorbed by
    # execute's bounded retry, and full engine resets (retries exhausted
    # or a stuck tick) that dropped all slot state for recompute-requeue
    engine_retries: int = 0
    engine_resets: int = 0
    # radix prompt cache (ISSUE 8): admissions whose prompt prefix was
    # aliased from cached pages instead of prefilled, the prompt tokens
    # those hits skipped, copy-on-write page copies (a hit ending inside
    # a page), and the teacher-forced catch-up tokens hit admissions
    # consumed through the decode dispatch (they ride `step` but are
    # prefill progress, not generated output — kept out of tokens_out)
    prefix_hits: int = 0
    prefix_hit_tokens: int = 0
    cow_copies: int = 0
    forced_catchup_tokens: int = 0
    # cross-request dedup (ISSUE 10): duplicate prompt-prefix pages a
    # row released at registration time by repointing its block table at
    # the radix cache's canonical pages — concurrent same-prefix
    # admissions double-fill pages the cache could not yet serve
    dedup_pages: int = 0
    # incremental chunk attention (ISSUE 9): continuation dispatches that
    # computed ONLY the new chunk against resident pages (no prefix
    # recompute) — each is also counted in chunk_prefills
    incr_chunks: int = 0
    # speculative decoding (ISSUE 9): draft tokens proposed, of which
    # accepted by the target's verify chunk, verify rounds run, and
    # rounds that rejected at least one draft token (rolled back to the
    # last accepted position)
    draft_tokens: int = 0
    accepted_tokens: int = 0
    spec_rounds: int = 0
    rollbacks: int = 0
    # blocking device->host reads (``InferenceEngine.host_sync``): each
    # holds the host until the device has run everything before it
    host_syncs: int = 0
    # misses of the engine's jit dictionaries: each builds a new step
    # executable, compiled (or loaded from the persistent cache) on its
    # first call
    executables_built: int = 0


class InferenceEngine:
    def __init__(self, api: ModelAPI, params, *, cache_len: int = 256,
                 mesh=None, donate_cache: bool = True,
                 alloc_chips: Optional[int] = None):
        self.api = api
        self.cfg = api.cfg
        self.params = params
        self.cache_len = cache_len
        self.mesh = mesh
        self.donate_cache = donate_cache
        # chip count of the sub-mesh this engine's executables are compiled
        # for — purely a label on this host, but the EnginePool keys standby
        # engines by it (the paper's re-allocation story: switching
        # allocation = switching to a pre-built engine, never recompiling)
        self.alloc_chips = alloc_chips
        self.stats = EngineStats()
        # fault tolerance (repro.serving.faults): injector armed at the
        # dispatch site of execute() and inside the page allocator;
        # transient dispatch faults retry up to retry_limit times with
        # exponential backoff before escalating to EngineFault
        self.fault_injector = None
        self.retry_limit = 2
        self.retry_backoff_s = 0.0
        # telemetry plane (repro.serving.telemetry): the engine's spans
        # are profiler annotations whether or not one is attached; an
        # attached Telemetry with a TraceRecorder also records them, and
        # the engine's instants, on this engine's track. Nothing blocks.
        self.telemetry = None
        self._track = Telemetry.engine_track(self)

        if mesh is not None:
            from jax.sharding import NamedSharding
            pspecs = api.param_specs(mesh)
            self._param_sh = jax.tree.map(partial(NamedSharding, mesh),
                                          pspecs)
        else:
            self._param_sh = None

        self._prefill_jit: Dict[int, Any] = {}
        self._gen_jit: Dict[Any, Any] = {}
        donate = (2,) if donate_cache else ()
        self._donate_cache_argnums = donate

        def dstack_decode_step(p, tok, cache):
            return api.decode_step(p, tok, cache)
        self._decode = jax.jit(dstack_decode_step, donate_argnums=donate)
        # one slot-step executable per sampling config (None = greedy);
        # built lazily, reused for every subsequent step
        self._slot_step_jit: Dict[Optional[SamplingParams], Any] = {}
        self._write_slot = jax.jit(dstack_write_slot, donate_argnums=(0,))
        self._write_slot_paged = None      # built by init_slots(paged=True)
        self._clear_slot = None
        self._clear_ring = None            # built by init_slots(paged=False)
        # packed ragged prefill: one executable per (total-token bucket,
        # row_len) pair — O(log max_len) total; built lazily
        self._packed_prefill_jit: Dict[Any, Any] = {}
        self._write_segments = None        # built by init_slots
        # chunk continuation (prefix recompute) reuses _packed_prefill_jit
        # and _write_segments — chunked serving compiles nothing new
        self._set_table_row = None         # built by init_slots(paged=True)
        # radix prompt cache (enable_prefix_cache): host-side radix tree
        # over the page allocator, plus the two static-shape executables
        # hit admissions dispatch — a COW page copy and the combined
        # table-row + position write
        self.prefix_cache = None
        self._copy_page = None
        self._alias_slot = None
        # incremental chunk attention (ISSUE 9): one executable per
        # (token bucket, row_len, segment bucket) triple, shared by
        # chunked-prefill continuations and speculative verification.
        # The slot cache rides in as a READ-ONLY operand (never donated):
        # only the chunk's own K/V comes back, and the segment scatter
        # commits it
        self._chunk_prefill_jit: Dict[Any, Any] = {}
        # speculative decoding (attach_draft): a paired ring engine
        # drafts spec_k tokens per round in one scanned dispatch; the
        # target verifies them in one chunk dispatch. _draft_ready holds
        # target slots whose draft twin is admitted (identity pairing:
        # target slot i drafts in draft slot i)
        self._draft: Optional["InferenceEngine"] = None
        self._draft_scan = None
        self._spec_commit = None
        self._draft_ready: set = set()
        self.spec_k = 0

        # slot state (populated by init_slots)
        self.paged = False
        self._kv: Optional[PagedKVCache] = None
        self._slot_cache = None
        self._slot_free: List[int] = []
        self._slot_active: List[bool] = []
        self._slot_budget: List[Optional[int]] = []
        self._slot_generated: List[int] = []
        self._slot_pos: List[int] = []      # host mirror of cache["pos"]
        self._slot_sampling: Optional[SamplingParams] = None
        self._slot_rng = None
        self._last_tok = None
        self._step_skip = frozenset()

    # ------------------------------------------------------------------
    def bucket_len(self, need: int) -> int:
        """Cache-length bucket for ``need`` tokens: next power of two,
        floored at the engine's base cache_len (compile once per bucket)."""
        return max(self.cache_len, _pow2_at_least(need))

    def new_cache(self, batch: int, cache_len: Optional[int] = None):
        return self.api.init_cache(batch, cache_len or self.cache_len)

    def prefill(self, batch: Dict[str, Any], cache_len: Optional[int] = None):
        clen = cache_len or self.cache_len
        fn = self._prefill_jit.get(clen)
        if fn is None:
            api = self.api

            def dstack_prefill(p, b):
                return api.prefill(p, b, clen)
            fn = jax.jit(dstack_prefill)
            self._prefill_jit[clen] = fn
            self._built(fn, clen)
        logits, cache = fn(self.params, batch)
        self.stats.prefills += 1
        self.stats.prefill_tokens += int(
            batch["tokens"].shape[0] * batch["tokens"].shape[1])
        return logits, cache

    def prefill_packed(self, packed: Dict[str, Any],
                       row_len: Optional[int] = None):
        """One dispatch over a packed batch of variable-length prompts.

        ``packed`` is the pytree ``_pack_prompts`` builds: ``tokens``
        (1, T) with T already bucketed to a power of two, ``seg_ids``
        (T,), ``seg_starts``/``seg_lens`` (S,) with S the pow2 bucket of
        the real segment count, plus ``enc_embeds`` for encoder models.
        Returns (per-segment last logits (S, V), packed cache). One
        executable per (T, row_len, S) triple — O(log³), and in practice
        near-additive because the three grow together.

        ``row_len`` defaults to the pow2 bucket of the batch's longest
        prompt (capped at slot_len), NOT slot_len itself: the fallback's
        per-segment row work (attention, conv, SSD) is quadratic/linear
        in row_len, and an engine with a long cache serving short
        prompts must not pay cache-sized rows per admission. The segment
        axis is bucketed for the same reason — a chunk continuation
        carrying one or two segments must not pay the full slot count's
        attention rows."""
        if row_len is None:
            row_len = min(self.slot_len, _pow2_at_least(int(self.host_sync(
                jnp.max(packed["seg_lens"]), "row_len"))))
        row_len = max(1, row_len)
        key = (packed["tokens"].shape[1], row_len,
               packed["seg_starts"].shape[0])
        fn = self._packed_prefill_jit.get(key)
        if fn is None:
            api = self.api

            def dstack_prefill_packed(p, pk):
                return api.prefill_packed(p, pk, row_len)
            fn = jax.jit(dstack_prefill_packed)
            self._packed_prefill_jit[key] = fn
            self._built(fn, key)
        logits, pcache = fn(self.params, packed)
        self.stats.prefills += 1
        self.stats.packed_prefills += 1
        self.stats.prefill_tokens += int(self.host_sync(
            jnp.sum(packed["seg_lens"]), "prefill_tokens"))
        return logits, pcache

    def prefill_chunk_packed(self, packed: Dict[str, Any],
                             row_len: Optional[int] = None):
        """One INCREMENTAL dispatch over a packed batch of continuation
        chunks: each segment's new tokens attend the K/V its slot already
        wrote into the page pool (through the slot's block-table row)
        plus the chunk itself causally — nothing before the chunk is
        recomputed. Same (T, row_len, S) bucket discipline as
        ``prefill_packed``; ``packed`` additionally carries ``seg_slots``
        (block-table rows to read) and ``hist_lens`` (tokens already
        resident per segment). Returns (per-segment last logits (S, V),
        per-token argmax (T,), packed cache) — the per-token argmax row
        is what speculative verification scores drafts against. Stats are
        charged by the callers (a continuation is prefill progress; a
        verify chunk is not)."""
        if row_len is None:
            row_len = min(self.slot_len, _pow2_at_least(int(self.host_sync(
                jnp.max(packed["seg_lens"]), "row_len"))))
        row_len = max(1, row_len)
        key = (packed["tokens"].shape[1], row_len,
               packed["seg_starts"].shape[0])
        fn = self._chunk_prefill_jit.get(key)
        if fn is None:
            api = self.api

            def dstack_prefill_chunk(p, pk, cache):
                return api.prefill_chunk(p, pk, cache, row_len)
            fn = jax.jit(dstack_prefill_chunk)
            self._chunk_prefill_jit[key] = fn
            self._built(fn, key)
        return fn(self.params, packed, self._slot_cache)

    def decode(self, token, cache):
        logits, cache = self._decode(self.params, token, cache)
        self.stats.decode_steps += 1
        return logits, cache

    # ------------------------------------------------------------------
    def _gen_fn(self, max_new_tokens: int, greedy: bool,
                sampling: SamplingParams):
        key = (max_new_tokens, greedy, sampling)
        fn = self._gen_jit.get(key)
        if fn is None:
            api = self.api

            def pick(rng, lg):
                if greedy:
                    return rng, jnp.argmax(lg, -1).astype(jnp.int32)
                rng, sub = jax.random.split(rng)
                return rng, L.sample_logits(
                    sub, lg, temperature=sampling.temperature,
                    top_k=sampling.top_k, top_p=sampling.top_p)

            def dstack_generate(params, logits, cache, rng):
                rng, tok0 = pick(rng, logits)

                def body(carry, _):
                    tok, cache, rng = carry
                    lg, cache = api.decode_step(params, tok, cache)
                    rng, nxt = pick(rng, lg)
                    return (nxt, cache, rng), tok

                (_, cache, _), toks = jax.lax.scan(
                    body, (tok0, cache, rng), None, length=max_new_tokens)
                # cache is returned (and discarded by the caller) so the
                # donated input can alias the output — true in-place reuse
                return toks.swapaxes(0, 1), cache           # (B, T), cache

            fn = jax.jit(dstack_generate,
                         donate_argnums=(2,) if self.donate_cache else ())
            self._gen_jit[key] = fn
            self._built(fn, key)
        return fn

    def generate(self, batch: Dict[str, Any], max_new_tokens: int,
                 greedy: bool = True, rng: Optional[jax.Array] = None,
                 sampling: Optional[SamplingParams] = None):
        """Prefill + one fused scan over all decode steps (single dispatch).

        Returns (B, max_new_tokens). Bit-equivalent to ``generate_eager``
        under greedy decoding. Passing ``sampling`` switches the scan body
        to temperature/top-k/top-p sampling (greedy is ignored); the
        sampler runs INSIDE the fused loop, so sampled generation still
        costs one dispatch per call. The scan length is bucketed to a
        power of two (like the cache length) so a stream of varying
        generation lengths compiles O(log) executables per sampling
        config, not one per distinct length; surplus tokens discarded."""
        if sampling is not None:
            greedy = False
        sampling = sampling or SamplingParams()
        b = batch["tokens"].shape[0]
        t_bucket = max(1, _pow2_at_least(max_new_tokens))
        need = batch["tokens"].shape[1] + t_bucket
        logits, cache = self.prefill(batch, self.bucket_len(need))
        if rng is None:
            rng = jax.random.PRNGKey(0)
        toks, _ = self._gen_fn(t_bucket, greedy, sampling)(
            self.params, logits, cache, rng)
        self.stats.decode_steps += t_bucket
        self.stats.tokens_out += b * max_new_tokens
        return toks[:, :max_new_tokens]

    def generate_eager(self, batch: Dict[str, Any], max_new_tokens: int,
                       greedy: bool = True, rng: Optional[jax.Array] = None):
        """Seed-engine reference path, kept as the bench_decode baseline and
        for parity tests: one jitted dispatch per token from a Python loop,
        and an UNBUCKETED exact-length prefill that re-traces/compiles
        whenever the request needs more than the base cache_len (the seed
        constructed a fresh ``jax.jit`` per such call)."""
        b = batch["tokens"].shape[0]
        need = max(self.cache_len, batch["tokens"].shape[1] + max_new_tokens)
        if need != self.cache_len:
            api = self.api

            def dstack_prefill_unbucketed(p, bt):
                return api.prefill(p, bt, need)
            fn = jax.jit(dstack_prefill_unbucketed)
            self._built(fn, need)
            logits, cache = fn(self.params, batch)
            self.stats.prefills += 1
        else:
            logits, cache = self.prefill(batch, self.cache_len)
        outs = []
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        for i in range(max_new_tokens):
            outs.append(tok)
            logits, cache = self.decode(tok, cache)
            if greedy:
                tok = jnp.argmax(logits, -1).astype(jnp.int32)
            else:
                rng, sub = jax.random.split(rng)
                tok = jax.random.categorical(sub, logits).astype(jnp.int32)
        self.stats.tokens_out += b * max_new_tokens
        return jnp.stack(outs, axis=1)

    # ------------------------------------------ slot continuous batching
    @property
    def n_slots(self) -> int:
        return 0 if self._slot_cache is None else len(self._slot_active)

    @property
    def free_slots(self) -> int:
        return len(self._slot_free)

    @property
    def free_pages(self) -> int:
        """Unallocated KV pages (0 when this engine has nothing to page —
        pure-SSM state is O(1), so pages never gate its admission)."""
        return self._kv.free_pages if self.paged else 0

    @property
    def total_pages(self) -> int:
        return self._kv.allocator.num_pages if self.paged else 0

    def init_slots(self, n_slots: int, cache_len: Optional[int] = None, *,
                   paged: bool = True, page_size: int = 8,
                   total_pages: Optional[int] = None,
                   sampling: Optional[SamplingParams] = None,
                   rng_seed: int = 0):
        """Allocate slot state for continuous batching.

        ``paged=True`` (default, for families with KV to page) backs the
        slots with a block-table page pool of ``total_pages`` usable pages
        (default ``n_slots * cache_len / page_size`` — same bytes as the
        rings it replaces; pass fewer pages and more slots to let mixed
        lengths share memory, which is the whole point). ``paged=False``
        keeps the original per-slot ring (the parity baseline).
        ``sampling`` fixes this engine's slot-step sampling config (None =
        greedy; each distinct config is one executable, compiled once).

        Sliding-window configs stay on ring slots even when ``paged`` is
        requested: the ring's overwrite IS the window, while a paged slot
        retains full history (pages never evict) and would silently widen
        the model's attention."""
        self.slot_len = cache_len or self.cache_len
        self.paged = (bool(paged) and bool(self.api.paged_keys)
                      and not getattr(self.cfg, "sliding_window", 0))
        # re-initializing slots invalidates any attached prefix cache
        # (page pool and page size may change) — re-enable explicitly
        self.prefix_cache = None
        self._copy_page = None
        self._alias_slot = None
        self._slot_sampling = sampling
        self._slot_rng = jax.random.PRNGKey(rng_seed)
        if self.paged:
            if self.slot_len % page_size:
                raise ValueError(
                    f"cache_len {self.slot_len} must be a multiple of "
                    f"page_size {page_size}")
            self.page_size = page_size
            self.max_pages = self.slot_len // page_size
            usable = total_pages or n_slots * self.max_pages
            self._kv = PagedKVCache(n_slots, page_size, self.max_pages,
                                    num_pages=usable)
            self._kv.allocator.fault_injector = self.fault_injector
            # +1 physical page: id 0 is the reserved null page
            self._slot_cache = self.api.init_paged_cache(
                n_slots, usable + 1, page_size, self.max_pages)
            self._write_slot_paged = jax.jit(
                _make_write_slot_paged(self.api.paged_keys, page_size),
                donate_argnums=(0,))
            self._clear_slot = jax.jit(dstack_clear_slot,
                                       donate_argnums=(0, 1))
            self._set_table_row = jax.jit(dstack_set_table_row,
                                          donate_argnums=(0,))
        else:
            self._kv = None
            self._slot_cache = self.api.init_cache(n_slots, self.slot_len)
            self._clear_ring = jax.jit(dstack_clear_ring,
                                       donate_argnums=(0, 1))
        # decode/chunk dispatches merge per-row cache leaves through a step
        # mask; page-indexed leaves (and the table, which decode never
        # writes) pass through — their dead writes land on the null page
        # or at a not-yet-valid position that is overwritten before read
        self._step_skip = (frozenset(self.api.paged_keys) | {"block_tables"}
                          if self.paged else frozenset())
        self._write_segments = jax.jit(
            _make_write_segments(self.api.paged_keys), donate_argnums=(0, 1))
        self._slot_free = list(range(n_slots))
        self._slot_active = [False] * n_slots
        self._slot_budget = [None] * n_slots
        self._slot_generated = [0] * n_slots
        self._slot_pos = [0] * n_slots
        self._active_mask = jnp.zeros((n_slots,), bool)
        self._last_tok = jnp.zeros((n_slots,), jnp.int32)
        return self

    # ------------------------------------------------ admission accounting
    def _need_tokens(self, prompt_len: int, n_tokens: Optional[int]) -> int:
        """KV entries a request pins: prompt + decode budget, capped at the
        slot maximum (an unbudgeted request reserves the full slot — the
        ring-equivalent worst case)."""
        cap = self.slot_len
        if n_tokens is None:
            return cap
        return min(cap, int(prompt_len) + max(1, int(n_tokens)))

    def pages_needed(self, prompt_len: int, n_tokens: Optional[int]) -> int:
        if not self.paged:
            return 0
        return self._kv.pages_needed(self._need_tokens(prompt_len, n_tokens))

    def can_admit(self, prompt_len: int, n_tokens: Optional[int]) -> bool:
        """Admission check: a free slot AND enough free pages for the
        request's whole horizon. Pages are reserved for the full prompt +
        budget up front (not grown lazily per step) so an admitted run can
        never deadlock mid-decode on a page it cannot get. Mirrors every
        condition ``insert`` enforces — including the paged requirement
        that the prompt leave decode room — so a True here can never turn
        into an insert-time exception."""
        if not self._slot_free:
            return False
        if not self.paged:
            return True
        if prompt_len >= self.slot_len:
            return False
        return self._kv.allocator.can_alloc(
            self.pages_needed(prompt_len, n_tokens))

    def insert(self, batch: Dict[str, Any],
               n_tokens: Optional[int] = None,
               reserve_tokens: Optional[int] = None) -> int:
        """Admit one request (batch size 1) into a free slot mid-stream.

        Prefills the prompt and writes the resulting cache into the slot —
        paged: scatter into freshly allocated pages + set the slot's block
        table row; ring: write the slot's rows. ``n_tokens`` is the
        request's decode budget: ``step`` reports the slot done after that
        many tokens, and (paged) only ``prompt + n_tokens`` worth of pages
        are claimed instead of the ring's full ``cache_len``.
        ``reserve_tokens`` overrides the page horizon claimed NOW (>= the
        prompt; the lazy planner reserves just the written tokens and
        ``grow_slot``s later). Raises ``OutOfPages`` (slot untouched) when
        the pool can't cover it."""
        if not self._slot_free:
            raise RuntimeError("no free slots")
        assert batch["tokens"].shape[0] == 1, "insert admits one request"
        s = batch["tokens"].shape[1]
        slot = self._slot_free[0]          # claim only after pages are ours
        if self.paged:
            if s >= self.slot_len:
                raise ValueError(
                    f"prompt of {s} tokens leaves no decode room in a "
                    f"{self.slot_len}-token paged slot (pages are never "
                    f"evicted; use a longer cache_len)")
            # unlike the ring (which wraps, sliding-window style), a paged
            # slot cannot outgrow its table: the budget is capped at the
            # page capacity so decode can never write past the last page
            room = self.slot_len - s
            budget = room if n_tokens is None else max(
                1, min(int(n_tokens), room))
            horizon = s + budget if reserve_tokens is None else max(
                s, min(int(reserve_tokens), self.slot_len))
            self._kv.alloc(slot, horizon)
            table_row = jnp.asarray(self._kv.table_row(slot), jnp.int32)
        else:
            budget = None if n_tokens is None else max(1, int(n_tokens))
        self._slot_free.pop(0)
        logits, one = self.prefill(batch, self.slot_len)
        if self.paged:
            self._slot_cache = self._write_slot_paged(
                self._slot_cache, one, jnp.int32(slot), table_row)
        else:
            self._slot_cache = self._write_slot(self._slot_cache, one,
                                                jnp.int32(slot))
        self._last_tok = self._last_tok.at[slot].set(
            jnp.argmax(logits[0], -1).astype(jnp.int32))
        self._slot_active[slot] = True
        self._slot_budget[slot] = budget
        self._slot_generated[slot] = 0
        self._slot_pos[slot] = s
        self._active_mask = self._active_mask.at[slot].set(True)
        self.stats.inserts += 1
        return slot

    # ------------------------------------------------ packed batch insert
    def _pack_prompts(self, batches: List[Dict[str, Any]],
                      lens: List[int]) -> Dict[str, Any]:
        """Concatenate an admission batch into one packed prompt row.

        Total tokens bucket to the next power of two (same O(log) compile
        discipline as ``generate``) and the segment axis buckets to the
        next power of two of the REAL segment count (the fallback's
        attention/conv/SSD row work is linear in the padded segment
        count — a one-segment chunk continuation must not pay the whole
        slot count's rows). Padding tokens carry segment id S (matched
        by no real token) and empty segments have length 0."""
        s_max = max(1, _pow2_at_least(len(batches)))
        t = max(1, _packed_bucket(sum(lens)))
        tokens = np.zeros((1, t), np.int32)
        seg_ids = np.full((t,), s_max, np.int32)
        starts = np.zeros((s_max,), np.int32)
        seg_lens = np.zeros((s_max,), np.int32)
        off = 0
        for i, (b, ln) in enumerate(zip(batches, lens)):
            tokens[0, off:off + ln] = self.host_sync(b["tokens"], "prompt")[0]
            seg_ids[off:off + ln] = i
            starts[i] = off
            seg_lens[i] = ln
            off += ln
        packed = {"tokens": jnp.asarray(tokens),
                  "seg_ids": jnp.asarray(seg_ids),
                  "seg_starts": jnp.asarray(starts),
                  "seg_lens": jnp.asarray(seg_lens)}
        if self.cfg.has_encoder:
            enc = [jnp.asarray(b["enc_embeds"]) for b in batches]
            pad = jnp.zeros_like(enc[0])
            packed["enc_embeds"] = jnp.concatenate(
                enc + [pad] * (s_max - len(enc)), axis=0)
        return packed

    def insert_many(self, batches: List[Dict[str, Any]],
                    n_tokens: Optional[List[Optional[int]]] = None,
                    reserve_tokens: Optional[List[Optional[int]]] = None
                    ) -> List[int]:
        """Admit a whole admission batch in ONE prefill dispatch.

        Semantically equivalent to calling ``insert`` once per request
        (same slots claimed in free-list order, same pages, bit-identical
        greedy decode afterwards) but the data plane does two dispatches
        total instead of 2 × batch: one packed ragged prefill over the
        concatenated prompts, and one token-indexed scatter that writes
        each segment's K/V DIRECTLY into its slot's pages (per-segment
        leaves — SSM state, conv tails, cross K/V, positions — take a
        batched row write in the same executable). Page allocation is
        all-or-nothing across the batch: on ``OutOfPages`` every page
        already claimed is returned and no slot is touched.
        ``reserve_tokens[i]`` (>= prompt i's length) overrides request
        i's page horizon — the StepPlanner's lazy-reservation knob."""
        n = len(batches)
        if n == 0:
            return []
        if n > len(self._slot_free):
            raise RuntimeError(
                f"insert_many of {n} requests, {len(self._slot_free)} "
                f"free slots")
        if n_tokens is None:
            n_tokens = [None] * n
        if reserve_tokens is None:
            reserve_tokens = [None] * n
        for b in batches:
            assert b["tokens"].shape[0] == 1, \
                "insert_many packs single-request batches"
        lens = [int(b["tokens"].shape[1]) for b in batches]
        budgets: List[Optional[int]] = []
        for s, nt in zip(lens, n_tokens):
            if self.paged:
                if s >= self.slot_len:
                    raise ValueError(
                        f"prompt of {s} tokens leaves no decode room in a "
                        f"{self.slot_len}-token paged slot (pages are never "
                        f"evicted; use a longer cache_len)")
                room = self.slot_len - s
                budgets.append(room if nt is None else max(
                    1, min(int(nt), room)))
            else:
                if s > self.slot_len:
                    raise ValueError(
                        f"prompt of {s} tokens exceeds the {self.slot_len}-"
                        f"token slot (packed prefill cannot ring-wrap)")
                budgets.append(None if nt is None else max(1, int(nt)))
        slots = self._slot_free[:n]
        if self.paged:
            claimed: List[int] = []
            try:
                for slot, s, budget, rsv in zip(slots, lens, budgets,
                                                reserve_tokens):
                    horizon = s + budget if rsv is None else max(
                        s, min(int(rsv), self.slot_len))
                    self._kv.alloc(slot, horizon)
                    claimed.append(slot)
            except OutOfPages:
                for slot in claimed:
                    self._kv.free(slot)
                raise
        del self._slot_free[:n]

        packed = self._pack_prompts(batches, lens)
        logits, pcache = self.prefill_packed(
            packed, row_len=min(self.slot_len, _pow2_at_least(max(lens))))
        args = self._segment_dest(slots, lens)
        self._slot_cache, self._last_tok = self._write_segments(
            self._slot_cache, self._last_tok, pcache, logits, *args)
        for slot, s, budget in zip(slots, lens, budgets):
            self._slot_active[slot] = True
            self._slot_budget[slot] = budget
            self._slot_generated[slot] = 0
            self._slot_pos[slot] = s
        self._active_mask = self._active_mask.at[
            jnp.asarray(slots, jnp.int32)].set(True)
        self.stats.inserts += n
        return slots

    def _segment_dest(self, slots: List[int], lens: List[int]):
        """Host-side destination indices for the packed-segment scatter.

        Per-token coordinates (dest0, dest1): (physical page, in-page
        offset) when paged — computed from the pages just allocated, so
        the prefill K/V lands straight in the page pool — or (slot row,
        column) for ring slots. Padding tokens target the null page
        (paged; duplicate writes there are dead by convention) or an
        out-of-bounds column (ring; scatter drops them). Per-segment
        coordinates are the slot ids, padded with ``n_slots`` (out of
        bounds, dropped)."""
        t = max(1, _packed_bucket(sum(lens)))
        # segment axis bucketed like _pack_prompts; padding entries carry
        # slot id n_slots — out of bounds on the SLOT axis, dropped
        s_max = max(1, _pow2_at_least(len(slots)))
        seg_slots = np.full((s_max,), self.n_slots, np.int32)
        seg_slots[:len(slots)] = slots
        if self.paged:
            dest0 = np.zeros((t,), np.int32)             # null page
            dest1 = np.zeros((t,), np.int32)
            tables = np.full((s_max, self.max_pages), NULL_PAGE, np.int32)
            off = 0
            for i, (slot, ln) in enumerate(zip(slots, lens)):
                pages = np.asarray(self._kv.pages(slot), np.int32)
                p = np.arange(ln)
                dest0[off:off + ln] = pages[p // self.page_size]
                dest1[off:off + ln] = p % self.page_size
                tables[i, :len(pages)] = pages
                off += ln
            table_rows = jnp.asarray(tables)
        else:
            dest0 = np.zeros((t,), np.int32)
            dest1 = np.full((t,), self.slot_len, np.int32)   # OOB: dropped
            off = 0
            for slot, ln in zip(slots, lens):
                dest0[off:off + ln] = slot
                dest1[off:off + ln] = np.arange(ln)
                off += ln
            table_rows = None
        return (jnp.asarray(dest0), jnp.asarray(dest1),
                jnp.asarray(seg_slots), table_rows)

    def _pack_chunks(self, batches: List[Dict[str, Any]], lens: List[int],
                     slots: List[int], hists: List[int]) -> Dict[str, Any]:
        """Pack continuation chunks for the incremental prefill: the
        regular packed-prompt row plus ``seg_slots`` (whose block-table
        row each segment reads its history through; padding carries
        ``n_slots``, clamped inside the model where its zero-length
        segment attends nothing) and ``hist_lens`` (tokens already
        resident; padding 0)."""
        packed = self._pack_prompts(batches, lens)
        s_max = packed["seg_starts"].shape[0]
        seg_slots = np.full((s_max,), self.n_slots, np.int32)
        seg_slots[:len(slots)] = slots
        hist = np.zeros((s_max,), np.int32)
        hist[:len(hists)] = hists
        packed["seg_slots"] = jnp.asarray(seg_slots)
        packed["hist_lens"] = jnp.asarray(hist)
        return packed

    def _segment_dest_at(self, slots: List[int], lens: List[int],
                         offs: List[int]):
        """``_segment_dest`` for continuation chunks: segment i's tokens
        land at positions ``offs[i] .. offs[i]+lens[i]`` of its slot
        (paged only — the incremental path requires resident pages).
        Table rows are the slot's CURRENT pages: the chunk's destination
        pages were reserved before the dispatch (admission horizon or an
        executed grow)."""
        assert self.paged
        t = max(1, _packed_bucket(sum(lens)))
        s_max = max(1, _pow2_at_least(len(slots)))
        seg_slots = np.full((s_max,), self.n_slots, np.int32)
        seg_slots[:len(slots)] = slots
        dest0 = np.zeros((t,), np.int32)             # null page
        dest1 = np.zeros((t,), np.int32)
        tables = np.full((s_max, self.max_pages), NULL_PAGE, np.int32)
        off = 0
        for i, (slot, ln, h) in enumerate(zip(slots, lens, offs)):
            pages = np.asarray(self._kv.pages(slot), np.int32)
            p = np.arange(h, h + ln)
            dest0[off:off + ln] = pages[p // self.page_size]
            dest1[off:off + ln] = p % self.page_size
            tables[i, :len(pages)] = pages
            off += ln
        return (jnp.asarray(dest0), jnp.asarray(dest1),
                jnp.asarray(seg_slots), jnp.asarray(tables))

    def free(self, slot: int) -> None:
        """Release a slot: its pages return to the pool, its block-table
        row parks on the null page, and its position pins to 0 (here and
        after every subsequent step) so vacant rows' dead writes land in
        the null page and their attention reads are masked to zero."""
        if not self._slot_active[slot]:
            return
        if slot in self._draft_ready:
            # the draft twin dies with its target
            self._draft.free(slot)
            self._draft_ready.discard(slot)
        self._slot_active[slot] = False
        self._slot_free.append(slot)
        self._slot_pos[slot] = 0
        if self.paged:
            self._kv.free(slot)
            self._slot_cache, self._active_mask = self._clear_slot(
                self._slot_cache, self._active_mask, jnp.int32(slot))
        else:
            cache = dict(self._slot_cache)
            cache["pos"], self._active_mask = self._clear_ring(
                cache["pos"], self._active_mask, jnp.int32(slot))
            self._slot_cache = cache

    # ------------------------------------------- radix prompt cache
    def prefix_cache_capable(self) -> bool:
        """A family can prefix-share iff pages + ``pos`` are a row's
        ENTIRE sequence state — i.e. the paged slot cache carries exactly
        the paged K/V leaves plus ``block_tables``/``pos``. Families with
        extra per-row leaves (SSM state, conv tails, cross K/V) fold the
        whole prefix into non-shareable state, so aliasing pages would
        not skip their prefill."""
        if not self.paged or self._slot_cache is None:
            return False
        extra = (set(self._slot_cache.keys())
                 - set(self.api.paged_keys) - {"block_tables", "pos"})
        return not extra

    # --------------------------------- incremental chunk / speculation
    def chunk_capable(self) -> bool:
        """A family takes the incremental continuation path iff its paged
        pages + ``pos`` are a row's entire sequence state (same criterion
        as the prefix cache — extra per-row leaves mean the prefix must
        be recomputed to carry the state forward), the family ships a
        ``prefill_chunk``, and it has no experts (the MoE packed-prefill
        caveat: per-token routing under segment masking is not yet
        bit-stable across packings — see tests/test_moe.py)."""
        if not self.prefix_cache_capable():
            return False
        if self.api.prefill_chunk is None:
            return False
        return not getattr(self.cfg, "num_experts", 0)

    def spec_capable(self) -> bool:
        """Speculative decoding additionally requires greedy slot
        sampling: draft/verify equivalence is an arg-max identity."""
        return self.chunk_capable() and self._slot_sampling is None

    def host_last_token(self, slot: int) -> int:
        """Host read of the slot's pending token (the next decode input,
        not yet emitted). The planner captures it once per request as the
        speculation seed; a per-slot sync, so gated on spec serving."""
        return int(self.host_sync(self._last_tok[slot], "last_token"))

    def draft_synced(self, slot: int) -> bool:
        """True when the slot's draft twin exists and sits at the same
        written-token position — the next spec round needs no re-init."""
        return (self._draft is not None and slot in self._draft_ready
                and self._draft._slot_pos[slot] == self._slot_pos[slot])

    def enable_prefix_cache(self):
        """Attach a radix prompt cache over this engine's page allocator
        and build the two hit-admission executables (COW page copy,
        combined table-row + position write). Raises for incapable
        families — callers that want best-effort use
        ``prefix_cache_capable`` first."""
        if not self.prefix_cache_capable():
            raise ValueError(
                f"{self.cfg.name}: prefix cache needs a paged engine whose "
                "per-row state is exactly pages + pos (families with SSM "
                "state / conv tails / cross K/V cannot alias their prefix)")
        from repro.serving.prefix_cache import PrefixCache
        self.prefix_cache = PrefixCache(self._kv.allocator, self.page_size)
        # recovery keeps radix nodes touched within this many cache
        # operations of the fault (``PrefixCache.retain_recent``) — the
        # hot working set survives an engine reset instead of flushing
        self.prefix_hot_window = 64
        if self._copy_page is None:
            self._copy_page = jax.jit(_make_copy_page(self.api.paged_keys),
                                      donate_argnums=(0,))
            self._alias_slot = jax.jit(dstack_alias_slot,
                                       donate_argnums=(0,))
        return self.prefix_cache

    def warm_prefix_ops(self) -> None:
        """Compile the hit-admission executables up front (the pool/bench
        0-recompile discipline): the COW copy warms null-page → null-page
        (dead by convention), the alias write warms against a vacant
        slot's existing parked state (null table row, position 0) so
        warming is a no-op on serving state."""
        if self.prefix_cache is None:
            return
        self._slot_cache = self._copy_page(
            self._slot_cache, jnp.int32(NULL_PAGE), jnp.int32(NULL_PAGE))
        if self._slot_free:
            slot = self._slot_free[0]
            null_row = jnp.full((self.max_pages,), NULL_PAGE, jnp.int32)
            self._slot_cache = self._alias_slot(
                self._slot_cache, jnp.int32(slot), null_row, jnp.int32(0))
            # registration-time dedup pushes repointed block-table rows
            # through set_table_row; warm it the same no-op way (a
            # vacant slot's row is already the null row) so a first
            # dedup after a jit-freeze snapshot cannot compile
            self._slot_cache = self._set_table_row(
                self._slot_cache, jnp.int32(slot), null_row)

    def slot_pages(self, slot: int) -> List[int]:
        """Physical pages backing a slot, in logical order (the prefix
        cache registers a finished prefill's leading pages)."""
        return self._kv.pages(slot) if self.paged else []

    def alias_admit(self, batch: Dict[str, Any], hit,
                    n_tokens: Optional[int] = None,
                    reserve_tokens: Optional[int] = None) -> int:
        """Admit one request whose prompt prefix is a cache hit — ZERO
        prefill dispatches for the covered tokens.

        ``hit`` is a pinned ``PrefixHit`` from ``prefix_cache.match``:
        its fully-covered pages alias read-only into the new slot's block
        table (the row adopts the match-time pins), a partial-page match
        is copied into the row's first fresh page (one static-shape COW
        dispatch; the pin on the source releases after the copy), and the
        remaining horizon allocates fresh pages all-or-nothing. The slot
        starts at ``pos = covered`` with ``last_tok`` = the first
        uncovered prompt token, so teacher-forced catch-up steps (the
        planner's ``StepPlan.forced``, or ``catchup_prefill``) replay the
        prompt tail through the regular decode dispatch — each step
        writes K/V at exactly the position whole-prompt prefill would
        have, and the final forced step leaves ``last_tok`` = argmax over
        the full prompt, exactly what ``insert`` seeds. Hit admissions
        are therefore bit-exact with cache-off admission by construction.

        Raises ``OutOfPages`` with nothing changed (the caller keeps the
        hit's pins and must ``release_hit`` it)."""
        if not self._slot_free:
            raise RuntimeError("no free slots")
        assert self.prefix_cache is not None, "enable_prefix_cache first"
        assert batch["tokens"].shape[0] == 1, "alias_admit admits one request"
        s = int(batch["tokens"].shape[1])
        covered = int(hit.covered)
        assert 0 < covered < s, \
            f"hit covers {covered} of a {s}-token prompt"
        if s >= self.slot_len:
            raise ValueError(
                f"prompt of {s} tokens leaves no decode room in a "
                f"{self.slot_len}-token paged slot (pages are never "
                f"evicted; use a longer cache_len)")
        room = self.slot_len - s
        budget = room if n_tokens is None else max(1, min(int(n_tokens),
                                                          room))
        horizon = s + budget if reserve_tokens is None else max(
            covered + 1, min(int(reserve_tokens), self.slot_len))
        slot = self._slot_free[0]          # claim only after pages are ours
        fresh = self._kv.alloc_alias(slot, hit.pages, horizon)
        self._slot_free.pop(0)
        if hit.cow_src is not None:
            # the partially-matched page copies into the row's first page
            # past the aliased prefix; the divergent suffix inside it is
            # stale but never read (attention masks by pos) and is
            # overwritten in order by the forced catch-up writes
            self._slot_cache = self._copy_page(
                self._slot_cache, jnp.int32(hit.cow_src),
                jnp.int32(fresh[0]))
            self._kv.allocator.release([hit.cow_src])
            self.stats.cow_copies += 1
        row = jnp.asarray(self._kv.table_row(slot), jnp.int32)
        self._slot_cache = self._alias_slot(
            self._slot_cache, jnp.int32(slot), row, jnp.int32(covered))
        toks = self.host_sync(batch["tokens"], "prompt")[0]
        self._last_tok = self._last_tok.at[slot].set(
            jnp.int32(int(toks[covered])))
        self._slot_active[slot] = True
        self._slot_budget[slot] = budget
        self._slot_generated[slot] = 0
        self._slot_pos[slot] = covered
        self._active_mask = self._active_mask.at[slot].set(True)
        self.stats.inserts += 1
        self.stats.prefix_hits += 1
        self.stats.prefix_hit_tokens += covered
        if self.telemetry is not None:
            self.telemetry.instant(
                self._track, "prefix_hit",
                slot=slot, covered=covered,
                cow=int(hit.cow_src is not None))
        return slot

    def catchup_prefill(self, slot: int, tokens, covered: int) -> None:
        """Teacher-forced completion of an aliased prompt, one decode
        dispatch per remaining token (the pool plane's eager form; the
        tick plane spreads the same steps across ticks via
        ``StepPlan.forced``). After the loop the slot sits exactly where
        a whole-prompt insert would: ``pos = len(tokens)``, ``last_tok``
        = argmax over the full prompt."""
        for i in range(int(covered), len(tokens)):
            self._last_tok = self._last_tok.at[slot].set(
                jnp.int32(int(tokens[i])))
            self.step([slot], forced={slot})

    def dedup_slot_prefix(self, slot: int, tokens, n_full: int) -> int:
        """Cross-request prefix dedup at registration time (ISSUE 10).

        When two same-prefix prompts prefill CONCURRENTLY, both miss at
        admission (the cache cannot serve what is not yet registered)
        and both fill their own pages with bit-identical K/V for the
        shared prefix (the PR-4 packed-prefill parity guarantee: a
        token's K/V depends only on the tokens before it). The first to
        finish registers its pages as the canonical ones; this call —
        made right after the SECOND registers — compares the slot's
        leading ``n_full`` pages against the tree's canonical walk and
        repoints every differing entry at the canonical page, releasing
        the row's duplicate (refcount 1 → actually freed). One
        pre-compiled ``set_table_row`` dispatch pushes the updated row;
        values never change (identical content), so streams are
        untouched. Safe because every later write on a registered row
        lands at ``pos >= prompt_len``, past the repointed prefix.
        Returns duplicate pages actually freed."""
        if not self.paged or self.prefix_cache is None or n_full < 1:
            return 0
        ps = self.page_size
        canonical = self.prefix_cache.canonical_pages(
            list(tokens)[:n_full * ps])
        own = self._kv.pages(slot)
        swaps = [(i, c) for i, (o, c)
                 in enumerate(zip(own[:n_full], canonical)) if o != c]
        if not swaps:
            return 0
        freed = self._kv.repoint(slot, swaps)
        row = jnp.asarray(self._kv.table_row(slot), jnp.int32)
        self._slot_cache = self._set_table_row(
            self._slot_cache, jnp.int32(slot), row)
        self.stats.dedup_pages += freed
        if self.telemetry is not None:
            self.telemetry.instant(
                self._track, "prefix_dedup",
                slot=slot, pages=freed)
        return freed

    # -------------------------------------------- lazy page reservation
    def slot_pos(self, slot: int) -> int:
        """Tokens written to the slot so far (host mirror of pos)."""
        return self._slot_pos[slot]

    def reserved_tokens(self, slot: int) -> int:
        """Token horizon the slot's pages currently cover (slot_len for
        ring/dense slots — they are fully backed by construction)."""
        if not self.paged:
            return self.slot_len
        return self._kv.length(slot)

    def slot_page_count(self, slot: int) -> int:
        return len(self._kv.pages(slot)) if self.paged else 0

    def kv_pages_needed(self, tokens: int) -> int:
        """Pages required to hold ``tokens`` KV entries (0 when unpaged)
        — the planner-facing page arithmetic of the PageView protocol."""
        return self._kv.pages_needed(max(1, int(tokens))) if self.paged \
            else 0

    def grow_slot(self, slot: int, upto_tokens: int) -> int:
        """Extend a resident slot's page horizon to cover ``upto_tokens``
        (lazy reservation: admission claimed only the written prefix).
        Newly crossed page boundaries allocate pages and push the updated
        block-table row to the device — one small pre-compiled dispatch,
        only when pages were actually added. Raises ``OutOfPages`` with
        the slot untouched (the planner's preemption signal). Returns the
        number of pages added."""
        if not self.paged:
            return 0
        have = self._kv.length(slot)
        delta = min(int(upto_tokens), self.slot_len) - have
        if delta <= 0:
            return 0
        fresh = self._kv.append(slot, delta)
        if fresh:
            row = jnp.asarray(self._kv.table_row(slot), jnp.int32)
            self._slot_cache = self._set_table_row(
                self._slot_cache, jnp.int32(slot), row)
            self.stats.grows += 1
        return len(fresh)

    def ensure_decode_room(self, slots) -> None:
        """Grow every slot to cover its next decode write (lazy pools call
        this before stepping; raises ``OutOfPages`` naming nothing —
        callers preempt a victim and retry)."""
        for slot in slots:
            self.grow_slot(slot, self._slot_pos[slot] + 1)

    # ------------------------------------------------- chunked prefill
    def chunk_append(self, chunks: List[Tuple[int, Dict[str, Any], bool]]
                     ) -> None:
        """Advance every mid-prefill slot by one chunk in ONE packed
        prefill dispatch (prefix recompute).

        ``chunks`` is [(slot, prefix pytree (1, done+chunk), final)] —
        each entry carries the request's FULL prompt prefix up to the end
        of this tick's chunk. The prefixes pack into one segmented row
        and run through the SAME ``prefill_packed`` executables
        admissions use (same pow2 token buckets — chunk continuation
        compiles nothing of its own), and ``_write_segments`` scatters
        every segment straight onto its slot: already-written prefix
        positions are REWRITTEN with bit-identical values (a token's K/V
        never depends on later tokens, and the packed fallback's exact-
        zero padding makes row-bucket size invisible — the PR-4 parity
        guarantee), the new chunk's tokens land on their pages for the
        first time, and the per-segment leaves (position, SSM state,
        conv tail, cross K/V) carry the partial segment forward as the
        recomputed post-prefix state. ``final`` segments leave
        ``last_tok`` = argmax of the full prompt's last logits — exactly
        what a one-shot insert seeds — so chunked prefill is bit-exact
        with whole-prompt admission by construction. The recompute costs
        O(prefix) extra FLOPs per chunk (the classic chunked-prefill
        trade: bounded per-tick work, decode never stalls on a burst).

        ``chunk_capable`` engines skip the recompute entirely
        (``stats.incr_chunks``): only the NEW tokens pack, and the
        incremental chunk attention kernel scores them against the K/V
        already resident in the slot's pages — O(chunk) per continuation
        instead of O(prefix + chunk). Each new position runs the same
        masked-decode attention body a decode step would, so the
        continuation stays exact with the whole-prompt admission it
        replaces."""
        if not chunks:
            return
        lens = []
        for slot, b, _ in chunks:
            ln = int(b["tokens"].shape[1])
            assert self._slot_active[slot], f"chunk into vacant slot {slot}"
            assert ln <= self.reserved_tokens(slot), \
                f"slot {slot}: chunk outruns its reserved pages"
            assert ln > self._slot_pos[slot], \
                f"slot {slot}: chunk makes no progress"
            lens.append(ln)
        slots = [slot for slot, _, _ in chunks]
        if self.chunk_capable():
            offs = [self._slot_pos[slot] for slot in slots]
            new_lens = [ln - off for ln, off in zip(lens, offs)]
            news = [{"tokens": jnp.asarray(
                self.host_sync(b["tokens"], "prompt")[:, off:ln])}
                for (_, b, _), off, ln in zip(chunks, offs, lens)]
            packed = self._pack_chunks(news, new_lens, slots, offs)
            seg_logits, _, pcache = self.prefill_chunk_packed(
                packed, row_len=min(self.slot_len,
                                    _pow2_at_least(max(new_lens))))
            args = self._segment_dest_at(slots, new_lens, offs)
            self._slot_cache, self._last_tok = self._write_segments(
                self._slot_cache, self._last_tok, pcache, seg_logits, *args)
            for slot, ln in zip(slots, lens):
                self._slot_pos[slot] = ln
            self.stats.prefills += 1
            self.stats.packed_prefills += 1
            self.stats.chunk_prefills += 1
            self.stats.incr_chunks += 1
            self.stats.prefill_tokens += sum(new_lens)
            return
        packed = self._pack_prompts([b for _, b, _ in chunks], lens)
        logits, pcache = self.prefill_packed(
            packed, row_len=min(self.slot_len, _pow2_at_least(max(lens))))
        args = self._segment_dest(slots, lens)
        self._slot_cache, self._last_tok = self._write_segments(
            self._slot_cache, self._last_tok, pcache, logits, *args)
        for slot, ln in zip(slots, lens):
            self._slot_pos[slot] = ln
        self.stats.chunk_prefills += 1

    # ------------------------------------------- speculative decoding
    def attach_draft(self, draft: "InferenceEngine", spec_k: int
                     ) -> "InferenceEngine":
        """Pair a small ring-slot draft engine with this (paged, greedy)
        target for speculative decoding: per spec round the draft
        proposes up to ``spec_k`` tokens in ONE scanned dispatch and the
        target verifies them all in ONE incremental chunk dispatch.

        Identity pairing — target slot i drafts in draft slot i — so the
        draft needs at least as many slots, each long enough to mirror a
        full target slot (ring wrap would corrupt the mirrored history).
        The ring never pages, so drafting can neither OutOfPages nor
        perturb the target's pool. Vocabularies must agree: accepted
        draft tokens feed the target's embedding directly."""
        if int(spec_k) < 1:
            raise ValueError("spec_k must be >= 1")
        if not self.spec_capable():
            raise ValueError(
                f"{self.cfg.name}: speculative decoding needs a paged "
                "greedy engine whose per-row state is exactly pages + pos "
                "and whose family ships prefill_chunk")
        if draft.paged:
            raise ValueError("draft must use ring slots (paged=False)")
        if draft.n_slots < self.n_slots or draft.slot_len < self.slot_len:
            raise ValueError(
                f"draft needs >= {self.n_slots} slots of >= "
                f"{self.slot_len} tokens (has {draft.n_slots} x "
                f"{getattr(draft, 'slot_len', 0)})")
        if draft.cfg.vocab_size != self.cfg.vocab_size:
            raise ValueError(
                f"draft/target vocabularies differ "
                f"({draft.cfg.vocab_size} vs {self.cfg.vocab_size})")
        self._draft = draft
        self.spec_k = int(spec_k)
        self._draft_ready = set()
        api, skip = draft.api, draft._step_skip

        # the whole draft round is one scanned dispatch: spec_k + 1
        # masked greedy decode-steps (the same body `step` runs), each
        # step's per-slot mask an input. Step i writes the previous
        # token's K/V and proposes the next; the final step exists only
        # to write the last proposal's K/V (its own proposal is
        # discarded) so an all-accepted round leaves the draft exactly
        # one teacher-forced bonus token behind the target.
        # the scan also assembles the verify-chunk token row ON DEVICE:
        # position 0 of each segment is the target's pending token,
        # positions 1..k its draft proposals. The host builds only the
        # (static) index vectors, so the verify dispatch queues
        # back-to-back behind the draft scan with no host sync — and no
        # separate gather dispatch — between them.
        def dstack_draft_scan(params, tok, cache, masks, tok_t, idx_t,
                              idx_d, step_idx, slot_idx):
            # teacher-force inside the dispatch: pin each paired row's
            # pending token to the target's — the host never reads
            # either engine's _last_tok to start a round
            tok = tok.at[idx_d].set(tok_t[idx_t], mode="drop")

            def body(carry, mask_t):
                tok, cache = carry
                tok, cache = _slot_decode_step(api, skip, params, tok,
                                               cache, mask_t)
                return (tok, cache), tok

            (tok, cache), props = jax.lax.scan(body, (tok, cache), masks)
            seed = tok_t[jnp.clip(slot_idx, 0, tok_t.shape[0] - 1)]
            drafted = props[jnp.maximum(step_idx - 1, 0), slot_idx]
            verify = jnp.where(step_idx == 0, seed, drafted)[None, :]
            return props, verify, tok, cache

        self._draft_scan = jax.jit(dstack_draft_scan, donate_argnums=(2,))

        # end-of-round commit fused into ONE dispatch: the packed-segment
        # scatter plus the fixups (pin both engines' pending tokens to
        # the bonus, rewind the draft ring to the accepted horizon) —
        # separately they cost three extra dispatch overheads per round.
        # All round-variable integers ride as ONE flat upload, sliced by
        # the (static) arg shapes: [bonus | draft pos | accepted pos |
        # dest pages/offsets].
        ws = _make_write_segments(self.api.paged_keys)

        def dstack_spec_commit(slot_cache, last_tok, lt_d, pos_d, pcache,
                               seg_logits, aux, segs, tables, idx_t, idx_d):
            n, s = idx_t.shape[0], segs.shape[0]
            gv, dp = aux[:n], aux[n:2 * n]
            new_pos = aux[2 * n:2 * n + s]
            dest = aux[2 * n + s:].reshape(2, -1)
            pcache = dict(pcache)
            pcache["pos"] = new_pos
            slot_cache, last_tok = ws(slot_cache, last_tok, pcache,
                                      seg_logits, dest[0], dest[1], segs,
                                      tables)
            return (slot_cache,
                    last_tok.at[idx_t].set(gv, mode="drop"),
                    lt_d.at[idx_d].set(gv, mode="drop"),
                    pos_d.at[idx_d].set(dp, mode="drop"))

        self._spec_commit = jax.jit(dstack_spec_commit,
                                    donate_argnums=(0, 1, 2, 3))
        self._spec_consts = {}
        self._spec_dest = {}
        return draft

    def _spec_round(self, entries: List[Tuple[int, int, Optional[List[int]]]],
                    res) -> None:
        """One draft → verify → accept/rollback round for the plan's
        ``spec`` entries [(slot, k, init_tokens-or-None)].

        Protocol (greedy): the target's pending token t sits at position
        P = ``_slot_pos[slot]`` with its K/V unwritten. The draft —
        teacher-forced to the same history — proposes d_1..d_k; the
        verify chunk runs [t, d_1..d_k] through the incremental prefill,
        whose per-token argmax row IS the sequence of tokens greedy
        decode would have emitted one step at a time. The longest prefix
        a of agreeing drafts is accepted, and position P+a's argmax is
        the bonus token — a+1 tokens emitted per round (so a round is
        never slower than the decode step it replaced). Commit rides the
        existing segment scatter with the packed ``pos`` overridden to
        the ACCEPTED horizon P+a+1: rejected positions' K/V land in the
        slot's reserved pages but sit past pos, never attended, and are
        rewritten in order before they ever matter — rollback costs zero
        dispatches and conserves pages. The draft rolls back the same
        way (ring pos rewind) and both ends hold the bonus token as
        their pending input, keeping the pair in lockstep for the next
        round."""
        tel = self.telemetry
        draft = self._draft
        slots = [s for s, _, _ in entries]
        offs = [self._slot_pos[s] for s in slots]

        # (re)admit draft twins that are missing or out of lockstep (the
        # slot decoded plainly while speculation was gated off): one
        # packed prefill on the DRAFT engine re-mirrors the history
        admit = []
        for (slot, _, init), off in zip(entries, offs):
            if self.draft_synced(slot):
                continue
            if slot in self._draft_ready:
                draft.free(slot)
                self._draft_ready.discard(slot)
            assert init is not None and len(init) == off, \
                f"slot {slot}: draft init missing or mismatched"
            admit.append((slot, init))
        if admit:
            order = [s for s, _ in admit]
            chosen = set(order)
            draft._slot_free = order + [s for s in draft._slot_free
                                        if s not in chosen]
            got = draft.insert_many(
                [{"tokens": jnp.asarray(np.asarray(toks, np.int32)[None, :])}
                 for _, toks in admit],
                n_tokens=[None] * len(admit))
            assert got == order, "draft twin landed on the wrong slot"
            self._draft_ready.update(order)
            res.dispatches += 1

        # round constants: every index vector, scan mask, and segment-
        # layout array depends only on (slots, ks) — identical for every
        # steady-state round — so each combination's host numpy and
        # device arrays build ONCE and replay. A spec round's HOST cost
        # is what bounds the speedup over plain per-token decode
        # (bench_decode --speculative measures exactly this), so the
        # per-round work must be O(changed state), not O(layout).
        ckey = (tuple(slots), tuple(k for _, k, _ in entries))
        consts = self._spec_consts.get(ckey)
        if consts is None:
            # index vectors pad to each engine's OWN slot count (out of
            # bounds, mode="drop"); the draft gets its own padding — it
            # may have more slots, so the target's n_slots could be a
            # live row there
            idx = np.full((self.n_slots,), self.n_slots, np.int32)
            idx[:len(slots)] = slots
            idxd = np.full((self.n_slots,), draft.n_slots, np.int32)
            idxd[:len(slots)] = slots
            n_steps = self.spec_k + 1
            m = np.zeros((n_steps, draft.n_slots), bool)
            for slot, k, _ in entries:
                m[:k + 1, slot] = True
            vlens = [k + 1 for _, k, _ in entries]
            t = max(1, _packed_bucket(sum(vlens)))
            s_max = max(1, _pow2_at_least(len(slots)))
            seg_ids = np.full((t,), s_max, np.int32)
            seg_starts = np.zeros((s_max,), np.int32)
            seg_lens = np.zeros((s_max,), np.int32)
            seg_slots = np.full((s_max,), self.n_slots, np.int32)
            seg_slots[:len(slots)] = slots
            step_np = np.zeros((t,), np.int32)
            slot_np = np.zeros((t,), np.int32)
            starts = []
            off = 0
            for j, (slot, k, _) in enumerate(entries):
                ln = k + 1
                seg_ids[off:off + ln] = j
                seg_starts[j] = off
                seg_lens[j] = ln
                step_np[off:off + ln] = np.arange(ln)
                slot_np[off:off + ln] = slot
                starts.append(off)
                off += ln
            consts = {
                "idx_j": jnp.asarray(idx), "idx_d": jnp.asarray(idxd),
                "mask": jnp.asarray(m), "n_steps": n_steps,
                "vlens": vlens, "t": t, "s_max": s_max, "starts": starts,
                "row_len": min(self.slot_len, _pow2_at_least(max(vlens))),
                "seg_ids": jnp.asarray(seg_ids),
                "seg_starts": jnp.asarray(seg_starts),
                "seg_lens": jnp.asarray(seg_lens),
                "seg_slots": jnp.asarray(seg_slots),
                "step_idx": jnp.asarray(step_np),
                "slot_idx": jnp.asarray(slot_np),
            }
            self._spec_consts[ckey] = consts
        idx_j, idx_d = consts["idx_j"], consts["idx_d"]
        vlens, starts = consts["vlens"], consts["starts"]
        t, s_max = consts["t"], consts["s_max"]

        # ---- draft: k+1 masked steps (teacher-forcing fused into the
        # scan prologue), one dispatch, nothing read back yet
        props, verify_tok, dtok, dcache = self._draft_scan(
            draft.params, draft._last_tok, draft._slot_cache,
            consts["mask"], self._last_tok, idx_j, idx_d,
            consts["step_idx"], consts["slot_idx"])
        draft._last_tok = dtok
        draft._slot_cache = dcache
        res.dispatches += 1

        # ---- verify: [t, d_1..d_k] per slot, one incremental chunk.
        # The token row is gathered from the draft's proposals ON
        # DEVICE, so the verify queues behind the scan without a host
        # sync and the two dispatches pipeline; only hist_lens (the
        # per-slot accepted horizon) uploads fresh each round
        hist = np.zeros((s_max,), np.int32)
        hist[:len(offs)] = offs
        packed = {
            "tokens": verify_tok,
            "seg_ids": consts["seg_ids"],
            "seg_starts": consts["seg_starts"],
            "seg_lens": consts["seg_lens"],
            "seg_slots": consts["seg_slots"],
            "hist_lens": jnp.asarray(hist),
        }
        seg_logits, tok_argmax, pcache = self.prefill_chunk_packed(
            packed, row_len=consts["row_len"])
        res.dispatches += 1

        # ---- accept / commit / rollback: the round's ONLY host reads —
        # both dispatches are already in flight when these block
        # per-slot proposal lists
        props_h = self.host_sync(props, "spec_proposals").T.tolist()
        amax = self.host_sync(tok_argmax, "spec_argmax").tolist()
        new_pos = np.zeros((s_max,), np.int32)
        gvals = np.zeros((self.n_slots,), np.int32)
        dpos = np.zeros((self.n_slots,), np.int32)
        emitted_total = accepted_total = drafted_total = n_roll = 0
        for j, (slot, k, _) in enumerate(entries):
            st = starts[j]
            pl = props_h[slot]
            a = 0
            while a < k and pl[a] == amax[st + a]:
                a += 1
            g = amax[st + a]                         # bonus token
            res.spec_tokens[slot] = pl[:a] + [g]
            new_pos[j] = offs[j] + a + 1
            gvals[j] = g
            dpos[j] = offs[j] + a + 1
            self._slot_pos[slot] = offs[j] + a + 1
            self._slot_generated[slot] += a + 1
            draft._slot_pos[slot] = offs[j] + a + 1
            emitted_total += a + 1
            accepted_total += a
            drafted_total += k
            if a < k:
                n_roll += 1
        # commit through the segment scatter with pos pinned to the
        # accepted horizon (rejected K/V sits past pos, never attended),
        # fused with the fixups — pending tokens pinned to the BONUS
        # (the scatter seeds argmax after P+k, not P+a), draft ring
        # rolled back to lockstep — in ONE dispatch. A resident slot's
        # pages are stable, so its table row uploads once per (slots,
        # pages) set; only the per-token dest coords (which track the
        # accepted horizon) re-upload each round.
        dkey = (ckey[0], self._kv.version)
        cached = self._spec_dest.get(dkey)
        if cached is None:
            if len(self._spec_dest) > 64:
                self._spec_dest.clear()
            pages_h = [np.asarray(self._kv.pages(s), np.int32)
                       for s in slots]
            tb = np.full((s_max, self.max_pages), NULL_PAGE, np.int32)
            for i, p in enumerate(pages_h):
                tb[i, :len(p)] = p
            cached = (pages_h, jnp.asarray(tb))
            self._spec_dest[dkey] = cached
        pages_h, tables = cached
        n = self.n_slots
        aux = np.zeros((2 * n + s_max + 2 * t,), np.int32)
        aux[:n], aux[n:2 * n] = gvals, dpos
        aux[2 * n:2 * n + s_max] = new_pos
        dest = aux[2 * n + s_max:].reshape(2, t)
        for i, (p, ln, h) in enumerate(zip(pages_h, vlens, offs)):
            span = np.arange(h, h + ln)
            dest[0, starts[i]:starts[i] + ln] = p[span // self.page_size]
            dest[1, starts[i]:starts[i] + ln] = span % self.page_size
        dc = dict(draft._slot_cache)
        (self._slot_cache, self._last_tok, draft._last_tok,
         dc["pos"]) = self._spec_commit(
            self._slot_cache, self._last_tok, draft._last_tok, dc["pos"],
            pcache, seg_logits, jnp.asarray(aux),
            consts["seg_slots"], tables, idx_j, idx_d)
        draft._slot_cache = dc

        self.stats.spec_rounds += 1
        self.stats.draft_tokens += drafted_total
        self.stats.accepted_tokens += accepted_total
        self.stats.rollbacks += n_roll
        self.stats.tokens_out += emitted_total
        for slot, active in enumerate(self._slot_active):
            if active:
                budget = self._slot_budget[slot]
                if (budget is not None
                        and self._slot_generated[slot] >= budget
                        and slot not in res.done):
                    res.done.append(slot)
        if tel is not None:
            tel.instant(self._track, "spec_round",
                        slots=len(slots), drafted=drafted_total,
                        accepted=accepted_total, rollbacks=n_roll)

    # ---------------------------------------------------- fault tolerance
    def attach_faults(self, injector, max_retries: Optional[int] = None,
                      backoff_s: Optional[float] = None) -> None:
        """Arm a ``FaultInjector`` at this engine's two fault sites: the
        dispatch site of ``execute`` and the page allocator (injected
        ``OutOfPages`` rides the existing all-or-nothing rollback paths).
        Attach AFTER warmup so the fault schedule is independent of
        compilation order. Pass ``injector=None`` to disarm."""
        self.fault_injector = injector
        if max_retries is not None:
            self.retry_limit = int(max_retries)
        if backoff_s is not None:
            self.retry_backoff_s = float(backoff_s)
        if self._kv is not None:
            self._kv.allocator.fault_injector = injector

    # ------------------------------------------------------- telemetry
    def attach_telemetry(self, tel) -> None:
        """Arm (or with None, disarm) the serving telemetry plane
        (``repro.serving.telemetry.Telemetry``) on this engine: its
        ``TraceRecorder`` then also records the engine's spans and
        instants. Recording never blocks on the device, so it changes no
        value, dispatch count or compilation."""
        self.telemetry = tel

    def host_sync(self, x, site: str) -> np.ndarray:
        """``x`` on the host. Every device-to-host read of the engine and
        its planner goes through here: a device array holds the host until
        the device has produced it, so such a read opens a
        ``dstack.engine.host_sync`` span naming its ``site`` and counts in
        ``stats.host_syncs``. A host array passes through uncounted."""
        if not isinstance(x, jax.Array):
            return np.asarray(x)
        with span(self.telemetry, self._track, "dstack.engine.host_sync",
                  site=site):
            out = np.asarray(x)
        self.stats.host_syncs += 1
        return out

    def _built(self, fn, key) -> None:
        """A miss of one of the engine's jit dictionaries: a new
        executable of the jitted step ``fn`` at bucket ``key``."""
        self.stats.executables_built += 1
        if self.telemetry is not None:
            self.telemetry.instant(self._track, "executable_built",
                                   cat="compile", step=fn.__name__,
                                   key=str(key))

    def recover(self) -> int:
        """Engine reset after an unrecoverable fault (retries exhausted,
        or a stuck tick whose dispatch was killed mid-flight): slot state
        on the device must be treated as lost, so every slot is freed —
        pages return to the pool, positions pin to 0 — and the page-
        conservation audit runs before serving resumes. Callers
        (planner/pool) recompute-requeue the evicted residents; recompute
        means surviving greedy streams replay bit-exactly.

        The radix prompt cache is NOT flushed (ISSUE 10): registered
        prefix pages hold only fully-written K/V from prompts that
        finished prefill before the fault — slot loss cannot have
        corrupted them (a faulted tick's writes target unregistered
        rows' pages) — so the hot subtree survives
        (``PrefixCache.retain_recent`` over ``prefix_hot_window``) and
        post-recovery admissions keep hitting. The conservation audit
        accounts the survivors: free + cache-held == total. Returns how
        many slots were dropped."""
        dropped = sum(1 for a in self._slot_active if a)
        self.release_all_slots(flush_cache=False)
        if self.prefix_cache is not None:
            self.prefix_cache.retain_recent(self.prefix_hot_window)
        if self.paged:
            held = (self.prefix_cache.held_pages
                    if self.prefix_cache is not None else 0)
            assert (self._kv.free_pages + held
                    == self._kv.allocator.num_pages), \
                "engine recovery leaked pages"
        self.check_page_invariants()
        self.stats.engine_resets += 1
        if self.telemetry is not None:
            self.telemetry.instant(self._track, "engine_reset",
                                   dropped=dropped)
        return dropped

    def check_page_invariants(self) -> bool:
        """Host-side page audit for the chaos suite: allocator
        conservation plus slot-level ownership (vacant slots own no
        pages, live rows match the allocator). No-op for ring engines."""
        if not self.paged:
            return True
        extra = (self.prefix_cache.page_refs()
                 if self.prefix_cache is not None else None)
        self._kv.check_invariants(extra_refs=extra)
        if self.prefix_cache is not None:
            self.prefix_cache.check_invariants()
        for slot in self._slot_free:
            assert not self._kv.pages(slot), \
                f"vacant slot {slot} still owns pages"
        return True

    # ------------------------------------------------- plan execution
    def execute(self, plan) -> "Any":
        """Run one ``StepPlan`` — the single data-plane entry point of
        the declarative serving API (``repro.serving.plan``). Fixed
        order: frees → cancels → preemptions → grows → first chunks (ONE
        packed prefill) → continuation chunks (ONE packed recompute
        prefill) → decodes (ONE slot step): at most three model
        dispatches per tick, all against pre-compiled executables.
        Returns a ``StepResult``.

        Fault tolerance: with a ``FaultInjector`` attached
        (``attach_faults``), injected ``TransientFault``s at the dispatch
        site retry up to ``retry_limit`` times with exponential backoff
        (``stats.engine_retries``); exhausted retries raise
        ``EngineFault`` — the control planes' engine-reset signal. The
        fault fires BEFORE the plan mutates anything, so a retried
        execute is indistinguishable from a clean one. Injected allocator
        failures surface in the result instead of raising: a failed
        admission batch (``admission_failed`` — insert_many rolled back
        all-or-nothing) or failed grows (``failed_grows`` — those slots
        are neither chunked nor decoded this tick); the planner requeues
        the affected requests under the recompute discipline, so their
        streams are unchanged when they are re-admitted."""
        attempts = 0
        while self.fault_injector is not None:
            try:
                self.fault_injector.maybe_fault("dispatch")
                break
            except TransientFault as e:
                self.stats.engine_retries += 1
                attempts += 1
                if self.telemetry is not None:
                    self.telemetry.instant(self._track, "retry",
                                           attempt=attempts)
                if attempts > self.retry_limit:
                    raise EngineFault(
                        f"dispatch fault persisted past {self.retry_limit} "
                        f"retries") from e
                if self.retry_backoff_s > 0:
                    import time
                    time.sleep(self.retry_backoff_s * (2 ** (attempts - 1)))
        with span(self.telemetry, self._track, "dstack.engine.execute",
                  admissions=len(plan.admissions), decodes=len(plan.decodes),
                  frees=len(plan.frees), cancels=len(plan.cancels),
                  preemptions=len(plan.preemptions), grows=len(plan.grows)):
            return self._execute_plan(plan)

    def _execute_plan(self, plan) -> "Any":
        """The plan's work, one ``dstack.engine.<part>`` span around the
        host packing and enqueue of each part (category ``dispatch``);
        the only waits are the ``host_sync`` reads inside them and the
        decode's token read-back after its span."""
        from repro.serving.plan import StepResult
        res = StepResult()
        for slot in plan.frees:
            self.free(slot)
        for slot in plan.cancels:
            self.free(slot)
        for slot in plan.preemptions:
            self.free(slot)
        tel, track = self.telemetry, self._track
        failed: set = set()
        if plan.grows:
            with span(tel, track, "dstack.engine.grow", cat="dispatch",
                      slots=len(plan.grows)):
                for slot, upto in plan.grows:
                    try:
                        self.grow_slot(slot, upto)
                    except OutOfPages:
                        # injected (or genuinely racy) allocator failure:
                        # the slot is untouched but its next write is
                        # unbacked — skip its chunk/decode this tick,
                        # report for requeue
                        failed.add(slot)
                        res.failed_grows.append(slot)
        alias = [c for c in plan.admissions
                 if c.slot is None and getattr(c, "alias", None) is not None]
        first = [c for c in plan.admissions
                 if c.slot is None and getattr(c, "alias", None) is None]
        cont = [c for c in plan.admissions if c.slot is not None
                and c.slot not in failed]
        for c in alias:
            # prefix-cache hit: zero model dispatches — at most one COW
            # page copy plus one table-row/pos write, both warm. Each hit
            # consumes its match-time pins; on OutOfPages (fresh tail
            # pages) nothing changed, so the pins return to the cache and
            # the planner requeues the request like any failed admission
            try:
                slot = self.alias_admit(c.batch, c.alias,
                                        n_tokens=c.n_tokens,
                                        reserve_tokens=c.reserve_tokens)
                res.admitted[c.rid] = slot
            except OutOfPages:
                self.prefix_cache.release_hit(c.alias)
                if tel is not None:
                    tel.instant(track, "alias_admission_failed", rid=c.rid)
        if first:
            try:
                with span(tel, track, "dstack.engine.prefill",
                          cat="dispatch", segs=len(first)):
                    slots = self.insert_many(
                        [c.batch for c in first],
                        n_tokens=[c.n_tokens for c in first],
                        reserve_tokens=[c.reserve_tokens for c in first])
                res.admitted.update(
                    {c.rid: s for c, s in zip(first, slots)})
                res.dispatches += 1
            except OutOfPages:
                # all-or-nothing rollback already ran: no slot was touched;
                # the planner requeues the whole staged batch
                res.admission_failed = True
                if tel is not None:
                    tel.instant(track, "admission_failed", segs=len(first))
        if cont:
            with span(tel, track, "dstack.engine.chunk", cat="dispatch",
                      segs=len(cont)):
                self.chunk_append([(c.slot, c.batch, c.final) for c in cont])
            res.dispatches += 1
        decodes = [s for s in plan.decodes if s not in failed]
        forced = [(s, t) for s, t in getattr(plan, "forced", [])
                  if s not in failed]
        if decodes or forced:
            with span(tel, track, "dstack.engine.decode", cat="dispatch",
                      slots=len(decodes), forced=len(forced)):
                # teacher-forced catch-up slots join THE decode dispatch:
                # the planner pre-picked this tick's prompt token per
                # slot; the masked step writes its K/V at pos (exactly
                # what prefill would write there) and advances pos.
                # Forced outputs never reach res.tokens — nothing was
                # generated for the stream
                for s, t in forced:
                    self._last_tok = self._last_tok.at[s].set(
                        jnp.int32(int(t)))
                toks, done = self.step(decodes + [s for s, _ in forced],
                                       forced={s for s, _ in forced})
            t = self.host_sync(toks, "decode")
            res.tokens = {int(s): int(t[s]) for s in decodes}
            res.done = list(done)
            res.dispatches += 1
        spec = [e for e in getattr(plan, "spec", ())
                if e[0] not in failed]
        if spec:
            # one span over the round: its read-backs are host_sync
            # children between the draft/verify enqueue and the commit
            with span(tel, track, "dstack.engine.spec", cat="dispatch",
                      slots=len(spec)):
                self._spec_round(spec, res)
        return res

    def _get_slot_step(self, sampling: Optional[SamplingParams]):
        fn = self._slot_step_jit.get(sampling)
        if fn is None:
            api = self.api
            skip = self._step_skip
            if sampling is None:
                def dstack_slot_step(p, tok, cache, active):
                    return _slot_decode_step(api, skip, p, tok, cache,
                                             active)
                fn = jax.jit(dstack_slot_step,
                             donate_argnums=self._donate_cache_argnums)
            else:
                def dstack_slot_step_sampled(p, tok, cache, active, rng):
                    return _slot_decode_step(api, skip, p, tok, cache,
                                             active, rng, sampling)
                fn = jax.jit(dstack_slot_step_sampled,
                             donate_argnums=self._donate_cache_argnums)
            self._slot_step_jit[sampling] = fn
            self._built(fn, sampling)
        return fn

    def step(self, slots: Optional[List[int]] = None,
             forced: Optional[set] = None
             ) -> Tuple[jax.Array, List[int]]:
        """One decode step in a single dispatch — for all active slots
        (default) or only the plan's ``decodes`` subset.

        Returns ``(tokens, done)``: tokens (n_slots,) with sampling (or
        greedy arg-max) already applied — entries for unstepped slots keep
        their previous value and must be ignored (``slot_active``) — and
        ``done``, the active slots whose per-request token budget is now
        exhausted (reported every step until the caller frees them). The
        done flags are host-side counters, so reading them never syncs
        the device. The step mask is an INPUT to one shared executable:
        stepping a subset (the plan API excludes mid-prefill slots)
        retraces nothing.

        Slots in ``forced`` are teacher-forced prompt catch-up (a prefix-
        cache hit replaying its uncovered tail): the caller pre-loaded
        the slot's ``last_tok`` with a prompt token, the step writes that
        token's K/V and advances ``pos`` exactly like prefill would, but
        the slot's generated counter — and the emitted-token accounting —
        are untouched: nothing was sampled for the stream."""
        if slots is None:
            mask = self._active_mask
            stepped = [s for s, a in enumerate(self._slot_active) if a]
        else:
            m = np.zeros((self.n_slots,), bool)
            for s in slots:
                m[s] = self._slot_active[s]
            mask = jnp.asarray(m)
            stepped = [s for s in slots if self._slot_active[s]]
        forced = forced or set()
        fn = self._get_slot_step(self._slot_sampling)
        if self._slot_sampling is None:
            tok, self._slot_cache = fn(
                self.params, self._last_tok, self._slot_cache, mask)
        else:
            self._slot_rng, sub = jax.random.split(self._slot_rng)
            tok, self._slot_cache = fn(
                self.params, self._last_tok, self._slot_cache, mask, sub)
        self._last_tok = tok
        n_forced = 0
        for slot in stepped:
            self._slot_pos[slot] += 1
            if slot in forced:
                n_forced += 1
            else:
                self._slot_generated[slot] += 1
        done: List[int] = []
        for slot, active in enumerate(self._slot_active):
            if active:
                budget = self._slot_budget[slot]
                if budget is not None and self._slot_generated[slot] >= budget:
                    done.append(slot)
        self.stats.decode_steps += 1
        self.stats.tokens_out += len(stepped) - n_forced
        self.stats.forced_catchup_tokens += n_forced
        return tok, done

    def slot_active(self, slot: int) -> bool:
        return self._slot_active[slot]

    def kv_cache_bytes(self) -> int:
        """Device bytes held by the slot cache (all leaves — the paged
        pool's block tables and the null page are charged too, so paged
        vs ring comparisons are honest)."""
        if self._slot_cache is None:
            return 0
        return int(sum(x.nbytes for x in jax.tree.leaves(self._slot_cache)))

    # --------------------------------------------- pool accounting hooks
    def release_all_slots(self, flush_cache: bool = True) -> None:
        """Force-free every slot (pool reset between policy runs), and
        restore the canonical free-list order for slots AND pages: a
        freed slot/page re-enters its list in free order, so without the
        re-sort a reset engine would hand out history-dependent ids —
        harmless for correctness (streams are slot-id agnostic) but
        fatal for exact replay (the chaos harness's determinism check
        replays a seeded fault schedule whose interleaving depends on
        deterministic tie-breaks over slot ids).

        ``flush_cache=True`` (the pool-reset default) also drops the
        prefix cache: a replayed seeded run must start from a cold
        cache (hit patterns are deterministic but history-dependent).
        ``recover()`` passes False — a mid-run engine reset keeps the
        hot radix working set (its own conservation audit accounts the
        cache-held pages)."""
        for slot, active in enumerate(self._slot_active):
            if active:
                self.free(slot)
        if self.prefix_cache is not None and flush_cache:
            self.prefix_cache.flush()
        self._slot_free.sort()
        if self.paged:
            self._kv.allocator.sort_free()
        if self._draft is not None:
            # freeing the targets freed their twins; restore the draft's
            # canonical free-list order too (same exact-replay argument)
            self._draft.release_all_slots()

    def reset_stats(self) -> None:
        """Zero the counters WITHOUT touching the jit caches — the pool
        warms executables once, then resets before the measured run."""
        self.stats = EngineStats()

    def jit_cache_sizes(self) -> Dict[str, int]:
        """Executable-cache cardinality, for asserting the pool's
        no-per-request-recompilation invariant. Counts traced signatures
        where jax exposes them (``_cache_size``), else cache-key entries."""
        def n(fn) -> int:
            try:
                return fn._cache_size()
            except (AttributeError, TypeError):
                # private jax API gone: fall back to counting the function
                # itself — new cache keys are still caught, intra-key
                # retraces are not. Warn so the no-recompilation check
                # can't degrade silently.
                import warnings
                warnings.warn(
                    "jax private _cache_size() unavailable; recompilation "
                    "accounting degrades to cache-key counting",
                    RuntimeWarning, stacklevel=2)
                return 1
        out = {
            "prefill": sum(n(f) for f in self._prefill_jit.values()),
            "packed_prefill": sum(
                n(f) for f in self._packed_prefill_jit.values()),
            "generate": sum(n(f) for f in self._gen_jit.values()),
            "decode": n(self._decode),
            "slot_step": sum(n(f) for f in self._slot_step_jit.values()),
            "write_slot": n(self._write_slot),
        }
        if self._write_segments is not None:
            out["write_segments"] = n(self._write_segments)
        if self._write_slot_paged is not None:
            out["write_slot_paged"] = n(self._write_slot_paged)
            out["clear_slot"] = n(self._clear_slot)
            out["set_table_row"] = n(self._set_table_row)
        if self._clear_ring is not None:
            out["clear_ring"] = n(self._clear_ring)
        if self._copy_page is not None:
            out["copy_page"] = n(self._copy_page)
            out["alias_slot"] = n(self._alias_slot)
        if self._chunk_prefill_jit:
            out["chunk_prefill"] = sum(
                n(f) for f in self._chunk_prefill_jit.values())
        if self._draft_scan is not None:
            out["draft_scan"] = n(self._draft_scan)
        if self._spec_commit is not None:
            out["spec_commit"] = n(self._spec_commit)
        return out


def _merge_rows(new, old, mask, skip):
    """Keep ``new`` cache leaves only for rows in ``mask``; rows outside
    it retain ``old`` bit-for-bit. Per-row leaves carry batch at axis 0
    (1-D ``pos``) or axis 1 (stacked ``(layers, B, ...)``) — the same
    layout rule ``dstack_write_slot`` relies on. Leaves in ``skip`` (paged K/V
    pools, the block table) are page-indexed, not row-indexed, and pass
    through: masked-off rows' dead writes there land at a not-yet-valid
    position (always overwritten before any read attends to it) or on
    the null page."""
    out = {}
    for key, nl in new.items():
        if key in skip:
            out[key] = nl
            continue
        axis = 0 if nl.ndim == 1 else 1
        shape = [1] * nl.ndim
        shape[axis] = mask.shape[0]
        out[key] = jnp.where(mask.reshape(shape), nl,
                             old[key].astype(nl.dtype))
    return out


def _slot_decode_step(api, skip, params, tok, cache, mask, rng=None,
                      sampling: Optional[SamplingParams] = None):
    logits, new = api.decode_step(params, tok, cache)
    # rows outside the step mask — vacant slots AND mid-prefill slots the
    # plan excluded — keep every per-row leaf (pos, SSM state, ring K/V)
    # bit-identical: an un-merged vacant row would creep back to
    # full-cache attention cost (ring) or walk off its null-page table
    # row (paged) within cache_len steps, and an advanced mid-prefill
    # row would corrupt its carried state
    cache = _merge_rows(new, cache, mask, skip)
    if sampling is None:
        nxt = jnp.argmax(logits, -1).astype(jnp.int32)
    else:
        nxt = L.sample_logits(rng, logits, temperature=sampling.temperature,
                              top_k=sampling.top_k, top_p=sampling.top_p)
    # unstepped rows keep their last token (a mid-prefill slot's pending
    # teacher-forced token must survive an interleaved decode dispatch)
    return jnp.where(mask, nxt, tok), cache


def dstack_write_slot(big, one, slot):
    """Write a batch-1 cache into row ``slot`` of a slotted cache. Every
    stacked leaf is (layers, batch, ...); the position vector is (batch,)."""
    def wr(b_leaf, o_leaf):
        o_leaf = o_leaf.astype(b_leaf.dtype)
        axis = 0 if b_leaf.ndim == 1 else 1
        return jax.lax.dynamic_update_slice_in_dim(b_leaf, o_leaf, slot,
                                                   axis=axis)
    return jax.tree.map(wr, big, one)


def _make_write_slot_paged(paged_keys, page_size: int):
    """Build the paged insert-scatter: paged leaves route the batch-1
    dense prefill cache through the slot's block-table row into the page
    pool; per-row leaves (pos, SSM state, cross K/V) take the dense row
    write. The table row is always the full padded (max_pages,) vector —
    one static shape, so a stream of varying prompt/budget page counts
    compiles exactly one executable (padding entries scatter their zeros
    into the never-read null page)."""
    paged_keys = frozenset(paged_keys)

    def dstack_write_slot_paged(big, one, slot, table_row):
        max_pages = table_row.shape[0]
        out = {}
        for key, b_leaf in big.items():
            if key == "block_tables":
                out[key] = jax.lax.dynamic_update_slice_in_dim(
                    b_leaf, table_row[None], slot, axis=0)
            elif key in paged_keys:
                o = one[key]                     # (layers, 1, slot_len, KV, D)
                o = o[:, 0].reshape(
                    (o.shape[0], max_pages, page_size) + o.shape[3:])
                o = o.transpose(0, 3, 1, 2, 4)   # head-major page pool
                out[key] = b_leaf.at[:, :, table_row].set(
                    o.astype(b_leaf.dtype))
            else:
                o_leaf = one[key].astype(b_leaf.dtype)
                axis = 0 if b_leaf.ndim == 1 else 1
                out[key] = jax.lax.dynamic_update_slice_in_dim(
                    b_leaf, o_leaf, slot, axis=axis)
        return out

    return dstack_write_slot_paged


def _make_write_segments(paged_keys):
    """Build the packed-insert scatter: per-TOKEN leaves (the family's
    ``PAGED_KEYS`` — packed (layers, T, ...) order) scatter each token at
    its (dest0, dest1) coordinate, which is (physical page, offset) on a
    paged cache — as (D,) rows at (layer, head, page, offset), so the
    pool is written in place — and (slot row, column) on a ring; every
    other leaf is per-SEGMENT and takes a batched row write at the slot
    ids. Padding tokens land on the null page (paged, dead by convention)
    or out of bounds (ring, dropped by scatter semantics); padding
    segments carry slot id n_slots (out of bounds, dropped). One
    static-shape executable per packed-token bucket — the batch's segment
    count never retraces."""
    paged_keys = frozenset(paged_keys)

    def dstack_write_segments(cache, last_tok, pcache, logits, dest0, dest1,
                              seg_slots, table_rows):
        paged = "block_tables" in cache
        out = {}
        for key, b_leaf in cache.items():
            if key == "block_tables":
                out[key] = b_leaf.at[seg_slots].set(table_rows)
            elif key in paged_keys:
                o = pcache[key].astype(b_leaf.dtype)   # (layers, T, KV, D)
                if paged:       # head-major pool (layers, KV, P, ps, D)
                    # one (D,) row per (layer, token, head): a (D,)
                    # window keeps the pool in place, where a (KV, D)
                    # one (``b_leaf.at[:, :, dest0, dest1]``) made the
                    # compiler copy the whole pool out and back
                    n_l, _, n_h, _ = o.shape
                    out[key] = b_leaf.at[
                        jnp.arange(n_l)[:, None, None],
                        jnp.arange(n_h)[None, None, :],
                        dest0[None, :, None], dest1[None, :, None]].set(o)
                else:           # ring (layers, slots, C, KV, D)
                    out[key] = b_leaf.at[:, dest0, dest1].set(o)
            else:
                o = pcache[key].astype(b_leaf.dtype)      # (layers, S, ...)
                axis = 0 if b_leaf.ndim == 1 else 1
                if axis == 0:
                    out[key] = b_leaf.at[seg_slots].set(o)
                else:
                    out[key] = b_leaf.at[:, seg_slots].set(o)
        new_last = last_tok.at[seg_slots].set(
            jnp.argmax(logits, -1).astype(jnp.int32))
        return out, new_last

    return dstack_write_segments


def dstack_set_table_row(cache, slot, table_row):
    """Push a grown slot's block-table row to the device (lazy page
    reservation: pages appear as decode/chunk writes cross page
    boundaries). One static shape — the row is always the full padded
    (max_pages,) vector — so growth never retraces."""
    cache = dict(cache)
    cache["block_tables"] = cache["block_tables"].at[slot].set(table_row)
    return cache


def _make_copy_page(paged_keys):
    """Build the copy-on-write page copy: every paged K/V leaf copies
    physical page ``src`` onto ``dst`` — one static-shape executable
    regardless of which pages are involved, so a stream of COW hits
    compiles exactly once. The alias path dispatches it at most once per
    hit admission (only when the match ends inside a page)."""
    paged_keys = frozenset(paged_keys)

    def dstack_copy_page(cache, src, dst):
        out = dict(cache)
        for key in sorted(paged_keys):
            leaf = out[key]            # (layers, KV, pages, page_size, D)
            page = jax.lax.dynamic_slice_in_dim(leaf, src, 1, axis=2)
            out[key] = jax.lax.dynamic_update_slice_in_dim(
                leaf, page, dst, axis=2)
        return out

    return dstack_copy_page


def dstack_alias_slot(cache, slot, table_row, pos):
    """Point a hit admission's slot at its aliased + fresh pages and set
    its position to the covered prefix length — the ONLY device writes a
    fully-page-aligned hit needs (COW adds one page copy). Like
    ``_set_table_row``, the row is the full padded (max_pages,) vector:
    one static shape for every hit."""
    cache = dict(cache)
    cache["block_tables"] = cache["block_tables"].at[slot].set(table_row)
    cache["pos"] = cache["pos"].at[slot].set(pos)
    return cache


def dstack_clear_slot(cache, mask, slot):
    """Park a freed slot: position 0 + whole table row on the null page,
    so its dead writes can never alias a page later granted to another
    sequence. The active-mask clear rides the same dispatch — a separate
    eager scatter costs a full dispatch overhead per free."""
    cache = dict(cache)
    cache["pos"] = cache["pos"].at[slot].set(0)
    cache["block_tables"] = cache["block_tables"].at[slot].set(NULL_PAGE)
    return cache, mask.at[slot].set(False)


def dstack_clear_ring(pos, mask, slot):
    """Ring-slot free: position and active-mask clear in one dispatch."""
    return pos.at[slot].set(0), mask.at[slot].set(False)


def make_engine(cfg, *, seed: int = 0, cache_len: int = 256
                ) -> InferenceEngine:
    """Engine over random weights from ``seed``, stored in ``cfg.dtype``
    (bf16 at published widths, fp32 for ``cfg.reduced()``)."""
    from repro.models.registry import build_model
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(seed), jnp.dtype(cfg.dtype))
    return InferenceEngine(api, params, cache_len=cache_len)
