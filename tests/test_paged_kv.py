"""Paged KV cache end to end: allocator, paged kernel parity, bit-exact
paged-vs-ring greedy decode across all four model families, page-gated pool
admission with ragged per-request budgets, and the shared event loop."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels import ref
from repro.kernels.chunk_attention import paged_chunk_attention
from repro.kernels.paged_attention import paged_decode_attention
from repro.models import layers as L
from repro.serving.engine import SamplingParams, make_engine
from repro.serving.kv_cache import (NULL_PAGE, OutOfPages, PageAllocator,
                                    PagedKVCache, pages_for)

KEY = jax.random.PRNGKey(11)


# ------------------------------------------------------------ page allocator
def test_allocator_alloc_free_roundtrip():
    a = PageAllocator(8)
    assert a.free_pages == 8 and a.used_pages == 0
    p1 = a.alloc(3)
    p2 = a.alloc(2)
    assert len(p1) == 3 and len(p2) == 2
    assert a.free_pages == 3
    # pages are distinct, never the null page
    assert len(set(p1) | set(p2)) == 5
    assert NULL_PAGE not in p1 + p2
    a.free(p1)
    assert a.free_pages == 6
    a.free(p2)
    assert a.free_pages == 8 and a.used_pages == 0


def test_allocator_out_of_pages_is_all_or_nothing():
    a = PageAllocator(4)
    a.alloc(3)
    with pytest.raises(OutOfPages):
        a.alloc(2)                      # only 1 free: must not partially grant
    assert a.free_pages == 1            # untouched by the failed alloc
    a.alloc(1)
    with pytest.raises(OutOfPages):
        a.alloc(1)


def test_allocator_fragmentation_is_harmless():
    """Interleaved alloc/free churn: any free page satisfies any request —
    full indirection means there is no contiguity to fragment."""
    a = PageAllocator(6)
    held = [a.alloc(2), a.alloc(2), a.alloc(2)]
    a.free(held[1])                     # free the MIDDLE allocation
    got = a.alloc(2)                    # must succeed from the "hole"
    assert sorted(got) == sorted(held[1])
    a.free(held[0])
    a.free(held[2])
    a.free(got)
    assert a.free_pages == 6


def test_allocator_double_free_and_null_page_rejected():
    a = PageAllocator(4)
    pages = a.alloc(2)
    a.free(pages)
    with pytest.raises(ValueError):
        a.free(pages)                   # double free
    with pytest.raises(ValueError):
        a.free([NULL_PAGE])


def test_paged_kv_cache_append_lazy_growth():
    kv = PagedKVCache(batch=2, page_size=4, max_pages=4, num_pages=6)
    kv.alloc(0, 5)                      # 5 tokens -> 2 pages
    assert kv.used_pages == 2 and kv.length(0) == 5
    assert kv.append(0, 3) == []        # 8 tokens still fit 2 pages
    fresh = kv.append(0, 1)             # 9th token crosses a page boundary
    assert len(fresh) == 1 and kv.used_pages == 3
    # row maximum enforced (4 pages * 4 slots = 16 tokens)
    with pytest.raises(OutOfPages):
        kv.append(0, 100)
    assert kv.length(0) == 9            # failed append left the row intact
    # out-of-pool growth signals too
    kv.alloc(1, 12)                     # 3 pages -> pool exhausted
    with pytest.raises(OutOfPages):
        kv.append(1, 8)
    assert kv.free(0) == 3
    assert kv.free(1) == 3
    assert kv.free_pages == 6
    assert kv.free(0) == 0              # idempotent


def test_pages_for():
    assert pages_for(0, 8) == 1         # live rows always own a page
    assert pages_for(1, 8) == 1
    assert pages_for(8, 8) == 1
    assert pages_for(9, 8) == 2


def test_table_row_fixed_shape():
    kv = PagedKVCache(batch=2, page_size=4, max_pages=4, num_pages=8)
    kv.alloc(0, 6)
    row = kv.table_row(0)
    assert len(row) == 4
    assert row[2:] == [NULL_PAGE, NULL_PAGE]
    assert all(p != NULL_PAGE for p in row[:2])


def test_random_churn_invariants_seeded():
    """Seeded-random alloc/append/free churn (the no-hypothesis sibling of
    tests/test_kv_properties.py): no page aliased by two live rows, page
    conservation, null page never allocated, failed ops all-or-nothing."""
    rng = np.random.default_rng(0)
    for trial in range(20):
        num_pages = int(rng.integers(4, 24))
        kv = PagedKVCache(batch=6, page_size=int(rng.choice([4, 8])),
                          max_pages=6, num_pages=num_pages)
        for _ in range(60):
            kind = int(rng.integers(0, 3))
            row = int(rng.integers(0, 6))
            amount = int(rng.integers(1, 40))
            before = (kv.free_pages, kv.length(row), tuple(kv.pages(row)))
            try:
                if kind == 0 and not kv.pages(row):
                    kv.alloc(row, amount)
                elif kind == 1 and kv.pages(row):
                    kv.append(row, amount)
                elif kind == 2:
                    kv.free(row)
            except OutOfPages:
                assert (kv.free_pages, kv.length(row),
                        tuple(kv.pages(row))) == before
            owned = [p for r in range(6) for p in kv.pages(r)]
            assert len(owned) == len(set(owned))          # no aliasing
            assert NULL_PAGE not in owned
            assert kv.free_pages + len(owned) == num_pages  # conservation
            for r in range(6):
                if kv.pages(r):
                    assert len(kv.pages(r)) == pages_for(kv.length(r),
                                                         kv.page_size)
            # the shipped audit (chaos suite's post-recovery check) must
            # agree with the independent re-derivation above
            kv.check_invariants()
        kv.reset()
        assert kv.free_pages == num_pages
        assert kv.check_invariants()


def test_shared_churn_invariants_seeded():
    """Seeded-random churn with a simulated radix-cache holder in the
    loop (the no-hypothesis sibling of ``test_shared_pages_random_churn``
    in tests/test_kv_properties.py): rows share a page only via the
    cache, refcounts conserve with the cache's holds declared, and a
    failed alias admission changes nothing (pins included)."""
    rng = np.random.default_rng(7)
    for trial in range(20):
        num_pages = int(rng.integers(4, 24))
        ps = int(rng.choice([4, 8]))
        kv = PagedKVCache(batch=6, page_size=ps, max_pages=6,
                          num_pages=num_pages)
        cache = {}             # page -> refs the simulated cache holds
        shared_origin = set()
        for _ in range(80):
            kind = int(rng.integers(0, 6))
            row = int(rng.integers(0, 6))
            amount = int(rng.integers(1, 40))
            before_free = kv.free_pages
            before = {r: (kv.length(r), tuple(kv.pages(r)))
                      for r in range(6)}
            before_cache = dict(cache)
            try:
                if kind == 0 and not kv.pages(row):
                    kv.alloc(row, amount)
                elif kind == 1 and kv.pages(row):
                    kv.append(row, amount)
                elif kind == 2:
                    kv.free(row)
                elif kind == 3 and kv.pages(row):
                    fresh = [p for p in kv.pages(row) if p not in cache]
                    kv.allocator.share(fresh)
                    cache.update({p: 1 for p in fresh})
                elif kind == 4 and not kv.pages(row) and cache:
                    held = sorted(cache)[:max(1, amount % (len(cache) + 1))]
                    tokens = min(len(held) * ps + 1 + amount % ps, 6 * ps)
                    if pages_for(tokens, ps) <= len(held):
                        continue
                    cow = None
                    if amount % 2 and len(cache) > len(held):
                        cow = sorted(cache)[len(held)]
                    kv.allocator.share(held)
                    if cow is not None:
                        kv.allocator.share([cow])
                    try:
                        kv.alloc_alias(row, held, tokens)
                        shared_origin.update(held)
                        if cow is not None:
                            kv.allocator.release([cow])
                    except OutOfPages:
                        kv.allocator.release(held)
                        if cow is not None:
                            kv.allocator.release([cow])
                        raise
                elif kind == 5 and cache:
                    drop = sorted(cache)[:max(1, amount % (len(cache) + 1))]
                    kv.allocator.release(drop)
                    for p in drop:
                        del cache[p]
            except OutOfPages:
                assert kv.free_pages == before_free
                assert cache == before_cache
                for r in range(6):
                    assert (kv.length(r), tuple(kv.pages(r))) == before[r]
            kv.check_invariants(extra_refs=dict(cache))
            owned = [p for r in range(6) for p in kv.pages(r)]
            multi = {p for p in owned if owned.count(p) > 1}
            assert multi <= shared_origin, multi - shared_origin
            assert kv.free_pages + len(set(owned) | set(cache)) == num_pages
        kv.allocator.release(list(cache))
        kv.reset()
        assert kv.free_pages == num_pages
        assert kv.check_invariants()


# --------------------------------------------------- paged kernel parity
PAGED_CASES = [
    # (b, h, kv, d, page_size, max_pages, lengths)
    (4, 8, 2, 64, 64, 4, [0, 77, 256, 130]),    # incl. empty + full rows
    (3, 4, 4, 64, 128, 1, [1, 128, 64]),        # single page
    (2, 14, 2, 64, 32, 8, [100, 3]),            # qwen2-like heads
    (5, 8, 1, 64, 128, 4, [0, 0, 512, 256, 511]),  # MQA, multiple empties
]


def _paged_setup(b, h, kv, d, ps, maxp, lengths, seed=0):
    """Random pages + a scrambled physical layout, and the contiguous
    logical view the oracle sees."""
    n_phys = b * maxp + 1
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, h, d))
    kp = jax.random.normal(ks[1], (kv, n_phys, ps, d))    # head-major pool
    vp = jax.random.normal(ks[2], (kv, n_phys, ps, d))
    rng = np.random.default_rng(seed)
    tables = rng.permutation(np.arange(1, n_phys))[:b * maxp] \
        .reshape(b, maxp).astype(np.int32)

    def logical(pool):
        rows = np.asarray(pool)[:, tables]            # (kv, b, maxp, ps, d)
        return jnp.asarray(rows.transpose(1, 2, 3, 0, 4)
                           .reshape(b, maxp * ps, kv, d))

    kc, vc = logical(kp), logical(vp)
    return q, kp, vp, jnp.asarray(tables), kc, vc


@pytest.mark.parametrize("b,h,kv,d,ps,maxp,lengths", PAGED_CASES)
def test_paged_kernel_matches_ref(b, h, kv, d, ps, maxp, lengths):
    q, kp, vp, tables, kc, vc = _paged_setup(b, h, kv, d, ps, maxp, lengths)
    lv = jnp.asarray(lengths, jnp.int32)
    out = paged_decode_attention(q, kp, vp, tables, lv, interpret=True)
    want = ref.decode_attention_ref(q, kc, vc, lv)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("b,h,kv,d,ps,maxp,lengths", PAGED_CASES)
def test_paged_fallback_matches_ragged(b, h, kv, d, ps, maxp, lengths):
    """The jnp gather fallback must agree with the contiguous ragged path
    bit-for-bit — same masked body, same reduction order."""
    q, kp, vp, tables, kc, vc = _paged_setup(b, h, kv, d, ps, maxp, lengths)
    lv = jnp.asarray(lengths, jnp.int32)
    paged = L.paged_decode_attention(q, kp, vp, tables, lv)
    contig = L.decode_attention(q, kc, vc, lv)
    np.testing.assert_array_equal(np.asarray(paged), np.asarray(contig))


def test_paged_null_page_rows_return_zero():
    q, kp, vp, tables, _, _ = _paged_setup(3, 8, 2, 64, 32, 4, [0, 5, 0])
    tables = tables.at[0].set(NULL_PAGE).at[2].set(NULL_PAGE)  # vacant rows
    lv = jnp.asarray([0, 5, 0], jnp.int32)
    out = np.asarray(L.paged_decode_attention(q, kp, vp, tables, lv))
    assert (out[0] == 0).all() and (out[2] == 0).all()
    assert np.abs(out[1]).sum() > 0


N_LAYERS = 3


def _stacked_pool(shape, rng):
    """A random (N_LAYERS,) + ``shape`` pool: every layer differs."""
    return jnp.asarray(rng.standard_normal((N_LAYERS,) + tuple(shape)),
                       jnp.float32)


@pytest.mark.parametrize("layer", range(N_LAYERS))
@pytest.mark.parametrize("b,h,kv,d,ps,maxp,lengths", PAGED_CASES)
def test_paged_kernel_stacked_pool_matches_layer(b, h, kv, d, ps, maxp,
                                                 lengths, layer):
    """The kernel reading a stacked (L, KV, P, ps, D) pool at ``layer``
    returns exactly what it returns on that layer's pool alone."""
    q, kp, _, tables, _, _ = _paged_setup(b, h, kv, d, ps, maxp, lengths)
    rng = np.random.default_rng(layer)
    ks, vs = _stacked_pool(kp.shape, rng), _stacked_pool(kp.shape, rng)
    lv = jnp.asarray(lengths, jnp.int32)
    out = paged_decode_attention(q, ks, vs, tables, lv, jnp.int32(layer),
                                 interpret=True)
    want = paged_decode_attention(q, ks[layer], vs[layer], tables, lv,
                                  interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


STACKED_CHUNK_CASES = [
    # (s, r, h, kv, d, page_size, max_pages, hists, slens)
    (2, 8, 4, 2, 32, 8, 3, (5, 16), (8, 3)),     # mid-page + aligned
    (3, 4, 8, 8, 32, 16, 2, (0, 13, 30), (4, 4, 2)),  # fresh seq, MHA
]


@pytest.mark.parametrize("layer", range(N_LAYERS))
@pytest.mark.parametrize("s,r,h,kv,d,ps,mp,hists,slens", STACKED_CHUNK_CASES)
def test_chunk_kernel_stacked_pool_matches_layer(s, r, h, kv, d, ps, mp,
                                                 hists, slens, layer):
    rng = np.random.default_rng(layer)
    n_pages = s * mp + 1
    q, kc, vc = (jnp.asarray(rng.standard_normal(shape), jnp.float32)
                 for shape in ((s, r, h, d), (s, r, kv, d), (s, r, kv, d)))
    ks = _stacked_pool((kv, n_pages, ps, d), rng)
    vs = _stacked_pool((kv, n_pages, ps, d), rng)
    tables = jnp.asarray((rng.permutation(n_pages - 1) + 1)[:s * mp]
                         .reshape(s, mp), jnp.int32)
    hist = jnp.asarray(hists, jnp.int32)
    slen = jnp.asarray(slens, jnp.int32)
    out = paged_chunk_attention(q, ks, vs, kc, vc, tables, hist, slen,
                                jnp.int32(layer), interpret=True)
    want = paged_chunk_attention(q, ks[layer], vs[layer], kc, vc, tables,
                                 hist, slen, interpret=True)
    for i in range(s):     # rows past a segment's length are padding
        n = slens[i]
        np.testing.assert_array_equal(np.asarray(out)[i, :n],
                                      np.asarray(want)[i, :n])


@pytest.mark.parametrize("layer", range(N_LAYERS))
def test_paged_cache_update_writes_rows_in_place(layer):
    """The row scatter writes each row's (KV, D) entry at (layer, head,
    page, slot) and leaves every other layer and page bit-identical;
    vacant rows land on the null page only."""
    kv, n_pages, ps, d = 4, 9, 8, 16
    rng = np.random.default_rng(layer)
    buf = _stacked_pool((kv, n_pages, ps, d), rng)
    new = jnp.asarray(rng.standard_normal((5, 1, kv, d)), jnp.float32)
    pages = np.array([3, 7, NULL_PAGE, 1, NULL_PAGE], np.int32)
    slots = np.array([0, 5, 0, 7, 0], np.int32)
    out = np.asarray(L.paged_cache_update(buf, new, jnp.int32(layer),
                                          jnp.asarray(pages),
                                          jnp.asarray(slots)))
    want = np.asarray(buf).copy()
    for row in (0, 1, 3):                      # the live rows
        want[layer, :, pages[row], slots[row]] = np.asarray(new)[row, 0]
    live = np.ones(n_pages, bool)
    live[NULL_PAGE] = False
    np.testing.assert_array_equal(out[:, :, live], want[:, :, live])
    others = [i for i in range(N_LAYERS) if i != layer]
    np.testing.assert_array_equal(out[others], np.asarray(buf)[others])


# ------------------------------------- paged vs ring engine parity (4 fams)
FAMILIES = ["olmo-1b", "mamba2-1.3b", "zamba2-7b", "whisper-small"]


def _prompt(cfg, i, s=8):
    b = {"tokens": jax.random.randint(jax.random.PRNGKey(100 + i), (1, s),
                                      0, cfg.vocab_size)}
    if cfg.has_encoder:
        from repro.serving import modality
        b["enc_embeds"] = modality.audio_frames(cfg, 1)
    return b


def _serve_stream(eng, cfg, budgets, n_steps=10):
    """Continuous batching with ragged budgets + churn; returns the greedy
    token stream of every slot at every step (active slots only)."""
    out = []
    nxt = 0
    for _ in range(n_steps):
        while nxt < len(budgets) and eng.can_admit(8, budgets[nxt]):
            eng.insert(_prompt(cfg, nxt), n_tokens=budgets[nxt])
            nxt += 1
        active = [s for s in range(eng.n_slots) if eng.slot_active(s)]
        tok, done = eng.step()
        out.append([(s, int(np.asarray(tok)[s])) for s in active])
        for s in done:
            eng.free(s)
    return out


@pytest.mark.parametrize("arch", FAMILIES)
def test_paged_matches_ring_greedy_mixed_lengths(arch):
    """THE acceptance bar: paged decode is bit-exact with ring-slot greedy
    decode on a mixed-length continuous-batching stream, per family."""
    cfg = get_config(arch).reduced()
    budgets = [3, 7, 2, 5, 4, 6]
    ring = make_engine(cfg, cache_len=32).init_slots(3, paged=False)
    pag = make_engine(cfg, cache_len=32).init_slots(3, paged=True,
                                                    page_size=8)
    ring_stream = _serve_stream(ring, cfg, budgets)
    paged_stream = _serve_stream(pag, cfg, budgets)
    assert ring_stream == paged_stream


def test_paged_engine_page_accounting_and_out_of_pages():
    cfg = get_config("olmo-1b").reduced()
    eng = make_engine(cfg, cache_len=32).init_slots(
        4, paged=True, page_size=8, total_pages=6)
    assert eng.total_pages == 6
    # prompt 8 + budget 8 = 16 tokens = 2 pages
    s0 = eng.insert(_prompt(cfg, 0), n_tokens=8)
    assert eng.free_pages == 4
    s1 = eng.insert(_prompt(cfg, 1), n_tokens=24)   # 32 tokens = 4 pages
    assert eng.free_pages == 0
    # free slots remain but NO pages: admission must be refused
    assert eng.free_slots == 2
    assert not eng.can_admit(8, 8)
    with pytest.raises(OutOfPages):
        eng.insert(_prompt(cfg, 2), n_tokens=8)
    assert eng.free_slots == 2          # failed insert left the slot free
    eng.free(s1)
    assert eng.free_pages == 4
    assert eng.can_admit(8, 8)
    eng.free(s0)
    assert eng.free_pages == 6


def test_paged_budget_capped_at_page_capacity():
    """A budget larger than the slot's page capacity is capped (pages are
    never evicted): the slot reports done AT capacity instead of writing
    past its last page, and a neighbor slot's stream is unperturbed —
    regression for the over-capacity corruption path."""
    cfg = get_config("olmo-1b").reduced()
    eng = make_engine(cfg, cache_len=16).init_slots(2, paged=True,
                                                    page_size=8)
    sa = eng.insert(_prompt(cfg, 0), n_tokens=100)   # room is 16 - 8 = 8
    sb = eng.insert(_prompt(cfg, 1), n_tokens=6)
    stream = []
    for i in range(8):
        tok, done = eng.step()
        stream.append(int(np.asarray(tok)[sa]))
        assert (sa in done) == (i >= 7)              # done at capacity
    solo = make_engine(cfg, cache_len=16).init_slots(2, paged=True,
                                                     page_size=8)
    sc = solo.insert(_prompt(cfg, 0), n_tokens=8)
    want = [int(np.asarray(solo.step()[0])[sc]) for _ in range(8)]
    assert stream == want
    # a prompt that leaves no decode room is rejected up front
    with pytest.raises(ValueError):
        make_engine(cfg, cache_len=16).init_slots(1, paged=True,
                                                  page_size=8).insert(
            _prompt(cfg, 0, s=16))


def test_paged_engine_unbudgeted_insert_reserves_full_slot():
    cfg = get_config("olmo-1b").reduced()
    eng = make_engine(cfg, cache_len=32).init_slots(
        2, paged=True, page_size=8)
    eng.insert(_prompt(cfg, 0))                      # no budget: ring-like
    assert eng.total_pages - eng.free_pages == 4     # all 32/8 pages


def test_step_done_flags_honor_budgets():
    cfg = get_config("olmo-1b").reduced()
    eng = make_engine(cfg, cache_len=32).init_slots(2, paged=True,
                                                    page_size=8)
    sa = eng.insert(_prompt(cfg, 0), n_tokens=2)
    sb = eng.insert(_prompt(cfg, 1), n_tokens=4)
    _, d1 = eng.step()
    assert d1 == []
    _, d2 = eng.step()
    assert d2 == [sa]                   # reported until freed
    _, d3 = eng.step()
    assert d3 == [sa]
    eng.free(sa)
    _, d4 = eng.step()
    assert d4 == [sb]


def test_freed_pages_reused_by_new_request_fresh():
    """A new request admitted into recycled pages must decode exactly as
    it would on a fresh engine (no ghost state in reused pages)."""
    cfg = get_config("olmo-1b").reduced()
    eng = make_engine(cfg, cache_len=32).init_slots(2, paged=True,
                                                    page_size=8)
    sa = eng.insert(_prompt(cfg, 0), n_tokens=3)
    sb = eng.insert(_prompt(cfg, 1), n_tokens=10)
    for _ in range(3):
        eng.step()
    eng.free(sa)
    sc = eng.insert(_prompt(cfg, 2), n_tokens=5)
    assert sc == sa
    got = [int(np.asarray(eng.step()[0])[sc]) for _ in range(5)]

    solo = make_engine(cfg, cache_len=32).init_slots(2, paged=True,
                                                     page_size=8)
    sd = solo.insert(_prompt(cfg, 2), n_tokens=5)
    want = [int(np.asarray(solo.step()[0])[sd]) for _ in range(5)]
    assert got == want


# ------------------------------------------------- sampling in slot step
def test_slot_step_sampling_zero_temperature_is_greedy():
    """Satellite regression: SamplingParams(temperature=0) through the
    slot step path must be bit-exact with the greedy slot step."""
    cfg = get_config("olmo-1b").reduced()
    g = make_engine(cfg, cache_len=32).init_slots(2, paged=True, page_size=8)
    s = make_engine(cfg, cache_len=32).init_slots(
        2, paged=True, page_size=8,
        sampling=SamplingParams(temperature=0.0))
    ga = g.insert(_prompt(cfg, 0))
    sa = s.insert(_prompt(cfg, 0))
    for _ in range(6):
        assert int(np.asarray(g.step()[0])[ga]) \
            == int(np.asarray(s.step()[0])[sa])


def test_slot_step_sampling_deterministic_and_in_vocab():
    cfg = get_config("olmo-1b").reduced()
    sp = SamplingParams(temperature=0.9, top_k=8)

    def stream(seed):
        eng = make_engine(cfg, cache_len=32).init_slots(
            2, paged=True, page_size=8, sampling=sp, rng_seed=seed)
        slot = eng.insert(_prompt(cfg, 0))
        return [int(np.asarray(eng.step()[0])[slot]) for _ in range(5)]

    a, b = stream(3), stream(3)
    assert a == b                       # same rng seed -> same stream
    assert all(0 <= t < cfg.padded_vocab for t in a)


# --------------------------------------------------- pool-level admission
def test_pool_admits_against_pages_and_counts_blocked():
    from repro.core.simulator import RunRequest
    from repro.serving.pool import build_pool
    from repro.serving.request import Request

    pool = build_pool(["olmo-1b"], base_slots=4, cache_len=32,
                      pages={"olmo-1b": 6})       # 6 pages < 4 slots * 4
    pool.reset()
    name = sorted(pool.hosts)[0]
    # 3 requests, budgets 8 -> (8 prompt + 8) = 2 pages each; only 3 fit
    # 6 pages, so with budget 24 (4 pages) the second blocks on memory
    pool.push(Request(arrival=0.0, rid=0, model=name, slo=1.0, n_tokens=24))
    pool.push(Request(arrival=0.0, rid=1, model=name, slo=1.0, n_tokens=24))
    pool.push(Request(arrival=0.0, rid=2, model=name, slo=1.0, n_tokens=8))
    run = pool.admit(RunRequest(name, chips=4096, batch=3), 0.0, gen_len=4)
    assert run is not None
    # 24-token budget = 4 pages; second 24-token ask exceeds the pool but
    # the 8-token one (2 pages) still fits behind it
    assert run.batch == 2
    m = pool._metrics[name]
    assert m.blocked_on_memory == 1
    assert len(pool.queues[name]) == 1
    # ragged budgets -> ragged completion: the short request frees first
    while not pool.step_run(run, 0.0):
        pass
    assert pool.queues[name].completed == 2
    pool.reset()


def test_pool_topup_refills_early_freed_slots():
    from repro.core.simulator import RunRequest
    from repro.serving.pool import build_pool
    from repro.serving.request import Request

    pool = build_pool(["olmo-1b"], base_slots=2, cache_len=32)
    pool.reset()
    name = sorted(pool.hosts)[0]
    # distinct arrivals pin the FIFO pop order (2-token, then 6-token)
    pool.push(Request(arrival=0.0, rid=0, model=name, slo=1.0, n_tokens=2))
    pool.push(Request(arrival=1e-4, rid=1, model=name, slo=1.0, n_tokens=6))
    pool.push(Request(arrival=2e-4, rid=2, model=name, slo=1.0, n_tokens=2))
    run = pool.admit(RunRequest(name, chips=4096, batch=2), 0.0, gen_len=4)
    assert run is not None and run.batch == 2
    # nothing to top up yet (no early frees)
    assert pool.topup(run, 0.0, 4) == 0
    pool.step_run(run, 0.0)
    finished = pool.step_run(run, 0.0)    # rid=0 (budget 2) completes here
    assert not finished and run.freed_early
    added = pool.topup(run, 0.0, 4)       # rid=2 refills the freed slot
    assert added == 1
    assert pool._metrics[name].topups == 1
    while not pool.step_run(run, 0.0):
        pass
    assert pool.queues[name].completed == 3
    pool.reset()


def test_ragged_workload_end_to_end_deterministic():
    """Mixed n_tokens stream through the full controller: determinism,
    ragged completions, and page occupancy all reported."""
    from repro.serving.controller import run_policy
    from repro.serving.pool import build_pool

    pool = build_pool(["qwen2-0.5b", "olmo-1b"], base_slots=2, cache_len=32)
    r1 = run_policy(pool, "dstack", rate=1500.0, duration=0.03,
                    gen_len=4, gen_tokens=(1, 8))
    r2 = run_policy(pool, "dstack", rate=1500.0, duration=0.03,
                    gen_len=4, gen_tokens=(1, 8))
    assert r1.total_completed == r2.total_completed > 0
    assert 0.0 <= r1.page_occupancy <= 1.0 + 1e-6
    assert not r1.truncated


# ----------------------------------------------- standby allocation set
def test_default_allocations_includes_midpoint_when_span_is_wide():
    import dataclasses as dc

    from repro.core.profiles import build_profile
    from repro.serving.pool import default_allocations

    prof = build_profile("olmo-1b")
    wide = dc.replace(prof, knee_chips=4, opt_chips=64)
    allocs = default_allocations(wide)
    mids = [a for a in allocs if 4 < a < 64]
    assert len(mids) == 1 and mids[0] == 16    # pow2 geometric mid point
    narrow = dc.replace(prof, knee_chips=8, opt_chips=16)
    assert [a for a in default_allocations(narrow) if 8 < a < 16] == []


def test_build_host_page_knobs():
    from repro.serving.pool import build_host

    host = build_host("olmo-1b", base_slots=3, cache_len=32, page_size=8,
                      total_pages=7)
    for alloc in host.allocations.values():
        assert alloc.engine.total_pages == 7
        assert alloc.engine.n_slots == 3


def test_build_pool_warms_with_oversubscribed_page_pool():
    """Regression: warmup must not reserve a full slot's pages — a pool
    deliberately built with fewer pages than one slot maximum (the
    oversubscription knob) used to crash with OutOfPages while warming."""
    from repro.serving.pool import build_pool

    pool = build_pool(["olmo-1b"], base_slots=4, cache_len=32,
                      pages={"olmo-1b": 3})      # 3 < 32/8 slot maximum
    name = sorted(pool.hosts)[0]
    for alloc in pool.hosts[name].allocations.values():
        assert alloc.engine.free_pages == 3      # warm state fully reset


# --------------------------------------------------- shared event loop
def test_event_loop_shared_by_simulator_and_controller():
    """Both planes implement EventLoopHooks and route run() through the
    one skeleton in repro.core.eventloop (no second copy to drift)."""
    import inspect

    from repro.core import eventloop
    from repro.core.simulator import Simulator
    from repro.serving.controller import Controller

    for plane in (Simulator, Controller):
        for hook in ("deliver", "next_completion", "advance", "fire",
                     "plan", "drained"):
            assert hasattr(plane, hook), (plane, hook)
        assert "run_event_loop" in inspect.getsource(plane.run)
    src = inspect.getsource(eventloop.run_event_loop)
    assert "max_time" in src and "drain" in src


def test_event_loop_truncates_on_max_events():
    from repro.core.eventloop import LoopConfig, run_event_loop

    class Hooks:
        def __init__(self):
            self.fired = 0

        def deliver(self, req):
            pass

        def next_completion(self):
            return self.fired * 0.1 + 0.1

        def next_wakeup(self, now):
            return float("inf")

        def advance(self, t):
            pass

        def fire(self, now, epsilon):
            self.fired += 1
            return 1

        def plan(self, now):
            pass

        def drained(self):
            return False

    out = run_event_loop(LoopConfig(duration=100.0, max_events=3), [],
                         Hooks())
    assert out.truncated and out.events == 3


class _TimedHooks:
    """Fires at a scripted list of times; records what fired and the
    furthest point the accumulators were advanced to."""

    def __init__(self, times):
        self.times = list(times)
        self.fired = []
        self.advanced = 0.0

    def deliver(self, req):
        pass

    def next_completion(self):
        return self.times[0] if self.times else float("inf")

    def next_wakeup(self, now):
        return float("inf")

    def advance(self, t):
        self.advanced = t

    def fire(self, now, epsilon):
        self.fired.append(self.times.pop(0))
        return 1

    def plan(self, now):
        pass

    def drained(self):
        return False


def test_event_loop_max_time_boundary():
    """Regression (ISSUE 6 satellite): the max_time backstop boundary is
    INCLUSIVE — an event exactly AT max_time fires; only events strictly
    past it truncate the run."""
    from repro.core.eventloop import LoopConfig, run_event_loop

    h = _TimedHooks([0.5, 1.0, 1.5])
    out = run_event_loop(
        LoopConfig(duration=100.0, drain=True, arrival_horizon=1e-9,
                   max_time=1.0), [], h)
    assert h.fired == [0.5, 1.0]          # the AT-boundary event fired
    assert out.truncated                  # 1.5 was beyond the backstop
    assert out.now == 1.0


def test_event_loop_max_time_truncation_advances_accumulators():
    """The max_time cutoff advances accumulators to the backstop before
    truncating — exactly like the duration cutoff — so ``out.now``
    always equals the window the partial integrals cover (previously
    they froze at the last fired event)."""
    from repro.core.eventloop import LoopConfig, run_event_loop

    h = _TimedHooks([0.25, 1.7])
    out = run_event_loop(
        LoopConfig(duration=100.0, drain=True, arrival_horizon=1e-9,
                   max_time=1.0), [], h)
    assert h.fired == [0.25]
    assert out.truncated
    assert out.now == 1.0                 # not 0.25: window is [0, 1.0]
    assert h.advanced == 1.0              # integrals cover the window too
