"""Packed ragged prefill: kernel parity, packed-vs-padded logit parity
across all four families, segment isolation, insert_many-vs-sequential-
insert equivalence on the paged cache, and the compile-count gate (packed
prefill adds O(log max_len) executables, not one per batch shape)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels import ref
from repro.kernels.flash_attention import segment_flash_attention
from repro.models import layers as L
from repro.models.registry import build_model
from repro.serving.engine import _packed_bucket, make_engine
from repro.serving.kv_cache import NULL_PAGE, OutOfPages

KEY = jax.random.PRNGKey(7)
FAMILIES = ["olmo-1b", "mamba2-1.3b", "zamba2-7b", "whisper-small"]
# packed and per-request prefill are different executables over the same
# fp32 twin: XLA fuses and orders their reductions differently, so values
# agree to reduction-order noise (measured <= 5.7e-7 on XLA-CPU), not bit
# for bit. 1e-5 is ~100 fp32 ulps at unit scale; a token attending the
# wrong segment or position moves logits by O(1e-2) or more
PARITY_TOL = 1e-5


def _pack(toks, s_max, t):
    """Host-side packing mirror of InferenceEngine._pack_prompts."""
    tokens = np.zeros((1, t), np.int32)
    seg = np.full((t,), s_max, np.int32)
    starts = np.zeros((s_max,), np.int32)
    lens = np.zeros((s_max,), np.int32)
    off = 0
    for i, tk in enumerate(toks):
        ln = tk.shape[1]
        tokens[0, off:off + ln] = np.asarray(tk)[0]
        seg[off:off + ln] = i
        starts[i] = off
        lens[i] = ln
        off += ln
    return {"tokens": jnp.asarray(tokens), "seg_ids": jnp.asarray(seg),
            "seg_starts": jnp.asarray(starts), "seg_lens": jnp.asarray(lens)}


def _prompt(cfg, i, s):
    b = {"tokens": jax.random.randint(jax.random.PRNGKey(100 + i), (1, s),
                                      0, cfg.vocab_size)}
    if cfg.has_encoder:
        from repro.serving import modality
        b["enc_embeds"] = modality.audio_frames(cfg, 1, seed=i)
    return b


# ------------------------------------------------------------ segment kernel
SEG_CASES = [
    # (T, lens, block, window)
    (256, [40, 17, 80, 3, 60], 64, 0),       # padding tail + tiny segments
    (256, [128, 128], 128, 0),               # exact tile boundaries
    (192, [1, 1, 190], 64, 0),               # single-token segments
    (256, [40, 17, 80, 3, 60], 64, 16),      # sliding window inside segments
    (768, [300, 200, 150, 100], 256, 0),     # half-step bucket, 256 tiles
]


def _seg_vector(t, lens):
    seg = np.full((t,), len(lens), np.int32)
    off = 0
    for i, ln in enumerate(lens):
        seg[off:off + ln] = i
        off += ln
    return jnp.asarray(seg)


@pytest.mark.parametrize("t,lens,block,window", SEG_CASES)
def test_segment_flash_kernel_matches_ref(t, lens, block, window):
    seg = _seg_vector(t, lens)
    ks = jax.random.split(KEY, 3)
    b, h, kv, d = 2, 4, 2, 64
    q = jax.random.normal(ks[0], (b, t, h, d))
    k = jax.random.normal(ks[1], (b, t, kv, d))
    v = jax.random.normal(ks[2], (b, t, kv, d))
    out = segment_flash_attention(q, k, v, seg, window=window,
                                  block_q=block, block_k=block,
                                  interpret=True)
    want = ref.packed_attention_ref(q, k, v, seg, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


def test_packed_attention_ref_accepts_batched_seg_ids():
    """The oracle takes (T,) or (B,T) seg ids — the same contract the
    kernel documents — and a (B,T) input equal per row matches (T,)."""
    t = 64
    seg = _seg_vector(t, [20, 30, 10])
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (2, t, 4, 32))
    k = jax.random.normal(ks[1], (2, t, 2, 32))
    v = jax.random.normal(ks[2], (2, t, 2, 32))
    one = ref.packed_attention_ref(q, k, v, seg)
    two = ref.packed_attention_ref(q, k, v, jnp.stack([seg, seg]))
    np.testing.assert_array_equal(np.asarray(one), np.asarray(two))


def test_packed_fallback_matches_ref():
    """The rows-gather CPU fallback and the kernel's reference agree on
    every real token (padding tokens are unspecified by contract)."""
    t, lens, row = 128, [40, 17, 33, 3], 64
    seg = _seg_vector(t, lens)
    starts = jnp.asarray(np.cumsum([0] + lens[:-1]), jnp.int32)
    slens = jnp.asarray(lens, jnp.int32)
    pos = L.packed_positions(seg, starts)
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, t, 4, 64))
    k = jax.random.normal(ks[1], (1, t, 2, 64))
    v = jax.random.normal(ks[2], (1, t, 2, 64))
    out = L.packed_prefill_attention(q, k, v, seg, pos, starts, slens,
                                     row_len=row)
    want = ref.packed_attention_ref(q, k, v, seg)
    real = sum(lens)
    np.testing.assert_allclose(np.asarray(out)[0, :real],
                               np.asarray(want)[0, :real], atol=2e-5)


def test_segments_to_rows_roundtrip():
    lens = [5, 0, 9, 2]
    t = 32
    starts = jnp.asarray(np.cumsum([0] + lens[:-1]), jnp.int32)
    slens = jnp.asarray(lens, jnp.int32)
    seg = _seg_vector(t, lens)
    pos = L.packed_positions(seg, starts)
    x = jax.random.normal(KEY, (t, 3))
    rows = L.segments_to_rows(x, starts, slens, 16)
    assert rows.shape == (4, 16, 3)
    # row i holds segment i's tokens then exact zeros (incl. empty seg 1)
    off = 0
    for i, ln in enumerate(lens):
        np.testing.assert_array_equal(np.asarray(rows)[i, :ln],
                                      np.asarray(x)[off:off + ln])
        assert (np.asarray(rows)[i, ln:] == 0).all()
        off += ln
    back = L.rows_to_segments(rows, seg, pos)
    real = sum(lens)
    np.testing.assert_array_equal(np.asarray(back)[:real],
                                  np.asarray(x)[:real])


# ----------------------------------------- packed vs padded prefill parity
@pytest.mark.parametrize("arch", FAMILIES)
def test_packed_prefill_bit_exact_per_family(arch):
    """THE acceptance bar: packed ragged prefill produces the last-token
    logits (not just the same argmax) of every segment that a per-request
    exact-length prefill produces, within ``PARITY_TOL``, in all four
    families."""
    cfg = get_config(arch).reduced()
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    lens = [5, 12, 3, 8]
    prompts = [_prompt(cfg, i, ln) for i, ln in enumerate(lens)]
    packed = _pack([p["tokens"] for p in prompts], s_max=6, t=32)
    if cfg.has_encoder:
        enc = [p["enc_embeds"] for p in prompts]
        packed["enc_embeds"] = jnp.concatenate(
            enc + [jnp.zeros_like(enc[0])] * (6 - len(enc)), axis=0)
    logits, pcache = api.prefill_packed(params, packed, 16)
    assert int(jnp.asarray(pcache["pos"])[0]) == lens[0]
    for i, p in enumerate(prompts):
        want, _ = api.prefill(params, p, 16)
        np.testing.assert_allclose(np.asarray(logits)[i],
                                   np.asarray(want)[0],
                                   rtol=PARITY_TOL, atol=PARITY_TOL)


@pytest.mark.parametrize("arch", ["olmo-1b", "mamba2-1.3b"])
def test_segment_isolation(arch):
    """A token in segment A never attends (or scans) across segment B:
    replacing every other segment's content leaves A's logits bit-equal,
    and A packed-alone equals A packed-with-neighbors."""
    cfg = get_config(arch).reduced()
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    a = _prompt(cfg, 0, 9)["tokens"]
    b1 = _prompt(cfg, 1, 6)["tokens"]
    b2 = _prompt(cfg, 2, 6)["tokens"]       # different neighbor content
    lg_b1, _ = api.prefill_packed(params, _pack([a, b1], 4, 16), 16)
    lg_b2, _ = api.prefill_packed(params, _pack([a, b2], 4, 16), 16)
    lg_solo, _ = api.prefill_packed(params, _pack([a], 4, 16), 16)
    np.testing.assert_array_equal(np.asarray(lg_b1)[0],
                                  np.asarray(lg_b2)[0])
    np.testing.assert_array_equal(np.asarray(lg_b1)[0],
                                  np.asarray(lg_solo)[0])
    # and the neighbor really did change ITS OWN logits
    assert not np.array_equal(np.asarray(lg_b1)[1], np.asarray(lg_b2)[1])


# ------------------------------------- insert_many vs sequential inserts
@pytest.mark.parametrize("arch", FAMILIES)
def test_insert_many_matches_sequential_inserts(arch):
    """One packed admission dispatch is bit-equivalent to a chain of
    per-request inserts: same slots, same greedy decode stream, same done
    flags — on the PAGED cache (the direct-to-pages path)."""
    cfg = get_config(arch).reduced()
    lens = [5, 12, 3, 8]
    seq = make_engine(cfg, cache_len=32).init_slots(6, paged=True,
                                                    page_size=8)
    pkd = make_engine(cfg, cache_len=32).init_slots(6, paged=True,
                                                    page_size=8)
    s_seq = [seq.insert(_prompt(cfg, i, ln), n_tokens=6)
             for i, ln in enumerate(lens)]
    s_pkd = pkd.insert_many([_prompt(cfg, i, ln)
                             for i, ln in enumerate(lens)],
                            n_tokens=[6] * len(lens))
    assert s_seq == s_pkd
    assert pkd.stats.prefills == 1 and pkd.stats.packed_prefills == 1
    assert seq.stats.prefills == len(lens)
    for _ in range(6):
        ta, da = seq.step()
        tb, db = pkd.step()
        assert da == db
        np.testing.assert_array_equal(np.asarray(ta)[s_seq],
                                      np.asarray(tb)[s_pkd])


def test_insert_many_ring_slots_match_sequential_inserts():
    """The same equivalence on RING slots (``paged=False``, the layout a
    speculative draft engine uses): the packed scatter writes each token
    at (slot row, column) of the ring cache, not into a page pool."""
    cfg = get_config("olmo-1b").reduced()
    lens = [5, 12, 3, 8]
    seq = make_engine(cfg, cache_len=32).init_slots(6, paged=False)
    pkd = make_engine(cfg, cache_len=32).init_slots(6, paged=False)
    s_seq = [seq.insert(_prompt(cfg, i, ln), n_tokens=6)
             for i, ln in enumerate(lens)]
    s_pkd = pkd.insert_many([_prompt(cfg, i, ln)
                             for i, ln in enumerate(lens)],
                            n_tokens=[6] * len(lens))
    assert s_seq == s_pkd
    for key in ("k", "v"):
        a, b = np.asarray(seq._slot_cache[key]), np.asarray(
            pkd._slot_cache[key])
        for slot, ln in zip(s_seq, lens):
            np.testing.assert_allclose(b[:, slot, :ln], a[:, slot, :ln],
                                       rtol=PARITY_TOL, atol=PARITY_TOL)
    for _ in range(6):
        ta, da = seq.step()
        tb, db = pkd.step()
        assert da == db
        np.testing.assert_array_equal(np.asarray(ta)[s_seq],
                                      np.asarray(tb)[s_pkd])


def test_insert_many_writes_identical_paged_cache():
    """Beyond token parity: the page pool CONTENTS after insert_many match
    sequential inserts leaf for leaf (the direct-to-pages scatter writes
    what the per-request dense scatter wrote). Tables and positions come
    from the same host bookkeeping and match exactly; K/V come from
    different prefill executables and match within ``PARITY_TOL``."""
    cfg = get_config("olmo-1b").reduced()
    lens = [5, 12, 3]
    seq = make_engine(cfg, cache_len=32).init_slots(4, paged=True,
                                                    page_size=8)
    pkd = make_engine(cfg, cache_len=32).init_slots(4, paged=True,
                                                    page_size=8)
    for i, ln in enumerate(lens):
        seq.insert(_prompt(cfg, i, ln), n_tokens=4)
    pkd.insert_many([_prompt(cfg, i, ln) for i, ln in enumerate(lens)],
                    n_tokens=[4] * len(lens))
    a, b = seq._slot_cache, pkd._slot_cache
    assert set(a) == set(b)
    np.testing.assert_array_equal(np.asarray(a["block_tables"]),
                                  np.asarray(b["block_tables"]))
    np.testing.assert_array_equal(np.asarray(a["pos"]), np.asarray(b["pos"]))
    for key in ("k", "v"):
        av, bv = np.asarray(a[key]), np.asarray(b[key])
        # compare only pages owned by live slots: the sequential path
        # zero-fills the rest of each slot's pages via its dense scatter,
        # the packed path never touches them (both are dead by the
        # lengths contract)
        for slot in range(3):
            for page in seq._kv.pages(slot):
                np.testing.assert_allclose(bv[:, :, page], av[:, :, page],
                                           rtol=PARITY_TOL, atol=PARITY_TOL)


def test_insert_many_out_of_pages_is_atomic():
    """If the batch cannot be fully paged, NOTHING is claimed: no pages,
    no slots, engine serves the next smaller batch untouched."""
    cfg = get_config("olmo-1b").reduced()
    eng = make_engine(cfg, cache_len=32).init_slots(4, paged=True,
                                                    page_size=8,
                                                    total_pages=5)
    free0 = eng.free_pages
    with pytest.raises(OutOfPages):
        # 2 pages + 4 pages > 5
        eng.insert_many([_prompt(cfg, 0, 8), _prompt(cfg, 1, 8)],
                        n_tokens=[8, 24])
    assert eng.free_pages == free0
    assert eng.free_slots == 4
    slots = eng.insert_many([_prompt(cfg, 0, 8)], n_tokens=[8])
    assert len(slots) == 1 and eng.free_pages == free0 - 2


def test_insert_many_rejects_oversized_prompts():
    cfg = get_config("olmo-1b").reduced()
    eng = make_engine(cfg, cache_len=16).init_slots(2, paged=True,
                                                    page_size=8)
    with pytest.raises(ValueError):
        eng.insert_many([_prompt(cfg, 0, 16)])    # no decode room
    assert eng.free_slots == 2 and eng.free_pages == eng.total_pages


def test_insert_many_then_free_then_reuse_is_fresh():
    """Recycled slots/pages after a packed admission decode exactly like a
    fresh engine — no ghost state from the packed scatter."""
    cfg = get_config("olmo-1b").reduced()
    eng = make_engine(cfg, cache_len=32).init_slots(2, paged=True,
                                                    page_size=8)
    slots = eng.insert_many([_prompt(cfg, 0, 5), _prompt(cfg, 1, 9)],
                            n_tokens=[3, 8])
    for _ in range(3):
        eng.step()
    eng.free(slots[0])
    (sc,) = eng.insert_many([_prompt(cfg, 2, 7)], n_tokens=[5])
    got = [int(np.asarray(eng.step()[0])[sc]) for _ in range(5)]
    solo = make_engine(cfg, cache_len=32).init_slots(2, paged=True,
                                                     page_size=8)
    (sd,) = solo.insert_many([_prompt(cfg, 2, 7)], n_tokens=[5])
    want = [int(np.asarray(solo.step()[0])[sd]) for _ in range(5)]
    assert got == want


# ------------------------------------------------------ compile-count gate
def test_packed_bucket_is_log_spaced():
    assert _packed_bucket(1) == 1
    assert _packed_bucket(5) == 6        # 3·2^1
    assert _packed_bucket(7) == 8
    assert _packed_bucket(96) == 96      # half-steps are exact
    assert _packed_bucket(97) == 128
    assert _packed_bucket(513) == 768
    # a whole octave maps onto two buckets
    assert {_packed_bucket(n) for n in range(65, 129)} == {96, 128}


def test_packed_prefill_compile_count_gate():
    """CI gate: a stream of admission batches with MANY distinct shapes
    (batch size × per-prompt lengths) must compile O(log max_len) packed
    executables — two per octave of total tokens, not one per batch."""
    cfg = get_config("olmo-1b").reduced()
    eng = make_engine(cfg, cache_len=32).init_slots(8, paged=True,
                                                    page_size=8)
    rng = np.random.default_rng(0)
    max_total = max_len = max_batch = 0
    for _ in range(12):
        n = int(rng.integers(1, 9))
        lens = rng.integers(2, 16, size=n).tolist()
        max_total = max(max_total, sum(lens))
        max_len = max(max_len, max(lens))
        max_batch = max(max_batch, n)
        slots = eng.insert_many([_prompt(cfg, i, ln)
                                 for i, ln in enumerate(lens)],
                                n_tokens=[1] * n)
        eng.step()
        for slot in slots:
            eng.free(slot)
    # executables key on (total-token bucket, row bucket, segment
    # bucket): two token buckets per octave, one row bucket per octave
    # of the longest prompt, one segment bucket per octave of the batch
    # size -> log + log + log, never one per batch shape
    bound = (2 * int(np.ceil(np.log2(max(2, max_total))))
             + int(np.ceil(np.log2(max(2, max_len))))
             + int(np.ceil(np.log2(max(2, max_batch)))) + 3)
    n_exec = len(eng._packed_prefill_jit)
    assert n_exec <= bound, (n_exec, bound)
    assert eng.jit_cache_sizes()["packed_prefill"] == n_exec
    # and the insert-side scatter retraces per bucket, never per batch
    assert eng.jit_cache_sizes()["write_segments"] <= bound


def test_engine_prefill_token_stats():
    """prefill_tokens counts REAL prompt tokens: the packed path is
    charged sum(lens), not the bucket."""
    cfg = get_config("olmo-1b").reduced()
    eng = make_engine(cfg, cache_len=32).init_slots(4, paged=True,
                                                    page_size=8)
    eng.insert_many([_prompt(cfg, 0, 5), _prompt(cfg, 1, 9)],
                    n_tokens=[1, 1])
    assert eng.stats.prefill_tokens == 14
    assert eng.stats.inserts == 2
