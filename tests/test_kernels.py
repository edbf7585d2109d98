"""Per-kernel validation: Pallas (interpret mode) and jnp production paths
vs the pure-jnp oracles in kernels/ref.py, swept over shapes and dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.flash_vjp import flash_attention_vjp

KEY = jax.random.PRNGKey(42)


def _mk_qkv(b, s, h, kv, d, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, s, h, d), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (b, s, kv, d), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (b, s, kv, d), jnp.float32).astype(dtype)
    return q, k, v


FLASH_SHAPES = [
    # (b, s, h, kv, d, block)
    (1, 128, 2, 2, 64, 64),
    (2, 256, 4, 2, 64, 128),
    (1, 256, 4, 1, 128, 64),       # MQA, wide head
    (2, 512, 8, 8, 64, 256),       # MHA
]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,s,h,kv,d,blk", FLASH_SHAPES)
def test_flash_attention_interpret_matches_ref(b, s, h, kv, d, blk, dtype):
    q, k, v = _mk_qkv(b, s, h, kv, d, dtype)
    out = ops.flash_attention(q, k, v, causal=True, block_q=blk, block_k=blk,
                              backend="interpret")
    want = ref.attention_ref(q, k, v, causal=True)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("window", [32, 128])
def test_flash_attention_window(window):
    q, k, v = _mk_qkv(2, 256, 4, 2, 64, jnp.float32)
    out = ops.flash_attention(q, k, v, causal=True, window=window,
                              block_q=64, block_k=64, backend="interpret")
    want = ref.attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


def test_flash_attention_jnp_matches_ref():
    q, k, v = _mk_qkv(2, 384, 6, 3, 64, jnp.float32)
    out = ops.flash_attention(q, k, v, causal=True, block_q=128, block_k=128,
                              backend="jnp")
    want = ref.attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


def test_flash_block_size_invariance():
    q, k, v = _mk_qkv(1, 256, 2, 2, 64, jnp.float32)
    outs = [ops.flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk,
                                backend="interpret")
            for bq, bk in [(64, 64), (128, 64), (256, 128)]]
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(o), np.asarray(outs[0]),
                                   atol=2e-5)


def test_flash_vjp_grads_match_ref():
    b, s, h, kv, d = 2, 128, 4, 2, 64
    q, k, v = _mk_qkv(b, s, h, kv, d, jnp.float32)
    ct = jax.random.normal(KEY, (b, s, h, d))

    def f_ref(q, k, v):
        kr = jnp.repeat(k, h // kv, 2)
        vr = jnp.repeat(v, h // kv, 2)
        return (ref.attention_ref(q, kr, vr, causal=True) * ct).sum()

    def f_new(q, k, v):
        return (flash_attention_vjp(q, k, v, causal=True,
                                    chunk_q=64, chunk_k=64) * ct).sum()

    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    g_new = jax.grad(f_new, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g_ref, g_new):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=1e-4)


# ---------------------------------------------------------------- SSD ----
SSD_SHAPES = [
    # (b, l, h, p, n, chunk)
    (1, 64, 2, 16, 8, 16),
    (2, 128, 4, 32, 16, 32),
    (1, 256, 2, 64, 64, 64),
    (2, 96, 3, 32, 16, 32),        # l not a multiple of chunk → padding path
]


@pytest.mark.parametrize("b,l,h,p,n,chunk", SSD_SHAPES)
def test_ssd_jnp_matches_sequential_oracle(b, l, h, p, n, chunk):
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (b, l, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, l, h)))
    a = -jnp.exp(0.5 * jax.random.normal(ks[2], (h,)))
    bb = jax.random.normal(ks[3], (b, l, n))
    cc = jax.random.normal(ks[4], (b, l, n))
    y_ref, s_ref = ref.ssd_ref(x, dt, a, bb, cc)
    y, s = ops.ssd(x, dt, a, bb, cc, chunk=chunk, backend="jnp")
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               atol=2e-3, rtol=2e-3)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref),
                               atol=2e-3, rtol=2e-3)


@pytest.mark.parametrize("b,l,h,p,n,chunk", SSD_SHAPES)
def test_ssd_pallas_interpret_matches_oracle(b, l, h, p, n, chunk):
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (b, l, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, l, h)))
    a = -jnp.exp(0.5 * jax.random.normal(ks[2], (h,)))
    bb = jax.random.normal(ks[3], (b, l, n))
    cc = jax.random.normal(ks[4], (b, l, n))
    y_ref, s_ref = ref.ssd_ref(x, dt, a, bb, cc)
    y, s = ops.ssd(x, dt, a, bb, cc, chunk=chunk, backend="interpret")
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               atol=2e-3, rtol=2e-3)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref),
                               atol=2e-3, rtol=2e-3)


@pytest.mark.parametrize("backend", ["jnp", "interpret"])
@pytest.mark.parametrize("l", [5, 37])
def test_ssd_ragged_length_with_initial_state(backend, l):
    """Serving hands the scan prompts of any length and, for a continued
    sequence, a carried-in state: both backends pad with dt = 0 (decay 1,
    update 0 — exact) and start from that state."""
    ks = jax.random.split(KEY, 6)
    b, h, p, n = 2, 3, 16, 8
    x = jax.random.normal(ks[0], (b, l, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, l, h)))
    a = -jnp.exp(0.5 * jax.random.normal(ks[2], (h,)))
    bb = jax.random.normal(ks[3], (b, l, n))
    cc = jax.random.normal(ks[4], (b, l, n))
    s0 = jax.random.normal(ks[5], (b, h, n, p))
    y_ref, s_ref = ref.ssd_ref(x, dt, a, bb, cc, initial_state=s0)
    y, s = ops.ssd(x, dt, a, bb, cc, chunk=16, backend=backend,
                   initial_state=s0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               atol=2e-3, rtol=2e-3)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref),
                               atol=2e-3, rtol=2e-3)


def test_ssd_chunk_size_invariance():
    ks = jax.random.split(KEY, 5)
    b, l, h, p, n = 1, 128, 2, 16, 8
    x = jax.random.normal(ks[0], (b, l, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, l, h)))
    a = -jnp.exp(0.5 * jax.random.normal(ks[2], (h,)))
    bb = jax.random.normal(ks[3], (b, l, n))
    cc = jax.random.normal(ks[4], (b, l, n))
    outs = [ops.ssd(x, dt, a, bb, cc, chunk=c, backend="jnp")[0]
            for c in (16, 32, 64, 128)]
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(o), np.asarray(outs[0]),
                                   atol=2e-3, rtol=2e-3)


def test_ssd_decode_matches_sequential():
    """Running ssd_decode token by token == the full-sequence oracle."""
    ks = jax.random.split(KEY, 5)
    b, l, h, p, n = 2, 32, 2, 16, 8
    x = jax.random.normal(ks[0], (b, l, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, l, h)))
    a = -jnp.exp(0.5 * jax.random.normal(ks[2], (h,)))
    bb = jax.random.normal(ks[3], (b, l, n))
    cc = jax.random.normal(ks[4], (b, l, n))
    y_ref, s_ref = ref.ssd_ref(x, dt, a, bb, cc)
    state = jnp.zeros((b, h, n, p))
    ys = []
    for t in range(l):
        y, state = ops.ssd_decode(x[:, t], dt[:, t], a, bb[:, t], cc[:, t],
                                  state)
        ys.append(y)
    y_dec = jnp.stack(ys, 1)
    np.testing.assert_allclose(np.asarray(y_dec), np.asarray(y_ref),
                               atol=2e-3, rtol=2e-3)
    np.testing.assert_allclose(np.asarray(state), np.asarray(s_ref),
                               atol=2e-3, rtol=2e-3)


# ------------------------------------------------------- decode attention
DECODE_SHAPES = [
    # (b, h, kv, d, cache, valid, block)
    (2, 8, 2, 64, 256, 200, 64),
    (1, 4, 4, 128, 512, 512, 128),
    (2, 14, 2, 64, 256, 100, 64),      # qwen2-like non-divisible heads
    (3, 8, 1, 64, 128, 77, 64),        # MQA
]


@pytest.mark.parametrize("b,h,kv,d,c,valid,blk", DECODE_SHAPES)
def test_decode_attention_kernel_matches_ref(b, h, kv, d, c, valid, blk):
    from repro.kernels.decode_attention import decode_attention
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, h, d))
    kc = jax.random.normal(ks[1], (b, c, kv, d))
    vc = jax.random.normal(ks[2], (b, c, kv, d))
    out = decode_attention(q, kc, vc, valid, block_k=blk, interpret=True)
    want = ref.decode_attention_ref(q, kc, vc, valid)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_kernel_dtypes(dtype):
    from repro.kernels.decode_attention import decode_attention
    ks = jax.random.split(KEY, 3)
    b, h, kv, d, c = 2, 8, 2, 64, 256
    q = jax.random.normal(ks[0], (b, h, d)).astype(dtype)
    kc = jax.random.normal(ks[1], (b, c, kv, d)).astype(dtype)
    vc = jax.random.normal(ks[2], (b, c, kv, d)).astype(dtype)
    out = decode_attention(q, kc, vc, 256, block_k=64, interpret=True)
    want = ref.decode_attention_ref(q, kc, vc, 256)
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


def test_flash_vjp_q_offset_matches_sliced_full():
    """Context-parallel building block: a q slice with q_offset must equal
    the same rows of full attention."""
    from repro.kernels.flash_vjp import flash_attention_vjp
    ks = jax.random.split(KEY, 3)
    b, s, h, d = 1, 256, 2, 64
    q = jax.random.normal(ks[0], (b, s, h, d))
    k = jax.random.normal(ks[1], (b, s, h, d))
    v = jax.random.normal(ks[2], (b, s, h, d))
    full = ref.attention_ref(q, k, v, causal=True)
    for lo in (0, 64, 128):
        part = flash_attention_vjp(q[:, lo:], k, v, causal=True,
                                   chunk_q=64, chunk_k=64, q_offset=lo)
        np.testing.assert_allclose(np.asarray(part),
                                   np.asarray(full[:, lo:]), atol=2e-5)


# ---------------------------------------------------------------------------
# incremental chunk attention (paged history + new chunk rows)
# ---------------------------------------------------------------------------
def _mk_chunk_case(seed, s, r, h, kv, d, page_size, max_pages, hists, slens):
    """One paged chunk-attention case plus its dense-ref twin.

    Pages are permuted non-contiguously across segments (each segment's
    block table scatters through the shared pool) so any confusion of
    physical/logical pages or cross-segment leakage shows up as a
    numeric mismatch, not a silent pass."""
    rng = np.random.default_rng(seed)
    n_pages = s * max_pages + 1              # +1: a never-referenced page
    q = rng.standard_normal((s, r, h, d), np.float32)
    kc = rng.standard_normal((s, r, kv, d), np.float32)
    vc = rng.standard_normal((s, r, kv, d), np.float32)
    # head-major pools (kv, pages, page_size, d)
    k_pages = rng.standard_normal((kv, n_pages, page_size, d), np.float32)
    v_pages = rng.standard_normal((kv, n_pages, page_size, d), np.float32)
    perm = rng.permutation(n_pages - 1) + 1  # page 0 never used: catches
    tables = perm[:s * max_pages].reshape(s, max_pages)  # accidental zeros
    cap = max_pages * page_size
    k_hist = k_pages[:, tables].transpose(1, 2, 3, 0, 4).reshape(s, cap, kv, d)
    v_hist = v_pages[:, tables].transpose(1, 2, 3, 0, 4).reshape(s, cap, kv, d)
    return (jnp.asarray(q), jnp.asarray(k_pages), jnp.asarray(v_pages),
            jnp.asarray(kc), jnp.asarray(vc), jnp.asarray(tables),
            jnp.asarray(hists, jnp.int32), jnp.asarray(slens, jnp.int32),
            jnp.asarray(k_hist), jnp.asarray(v_hist))


CHUNK_SHAPES = [
    # (s, r, h, kv, d, page_size, max_pages, hists, slens)
    (2, 4, 4, 2, 32, 8, 3, (8, 16), (4, 4)),     # page-aligned histories
    (3, 8, 4, 4, 32, 8, 4, (5, 13, 0), (8, 3, 6)),  # mid-page + fresh seq
    (1, 16, 8, 2, 64, 16, 2, (13,), (16,)),      # chunk crosses a page edge
    (2, 8, 2, 1, 32, 8, 2, (1, 7), (1, 8)),      # MQA, ragged seg lens
]


@pytest.mark.parametrize("s,r,h,kv,d,ps,mp,hists,slens", CHUNK_SHAPES)
def test_chunk_attention_interpret_matches_ref(s, r, h, kv, d, ps, mp,
                                               hists, slens):
    from repro.kernels.chunk_attention import paged_chunk_attention
    case = _mk_chunk_case(0, s, r, h, kv, d, ps, mp, hists, slens)
    q, kp, vp, kc, vc, tbl, hist, slen, kh, vh = case
    out = paged_chunk_attention(q, kp, vp, kc, vc, tbl, hist, slen,
                                interpret=True)
    want = ref.chunk_attention_ref(q, kh, vh, kc, vc, hist)
    for i in range(s):
        n = int(slen[i])
        np.testing.assert_allclose(
            np.asarray(out)[i, :n], np.asarray(want)[i, :n],
            atol=2e-5, rtol=2e-5, err_msg=f"segment {i}")


@pytest.mark.parametrize("window", [4, 16])
def test_chunk_attention_interpret_window(window):
    from repro.kernels.chunk_attention import paged_chunk_attention
    case = _mk_chunk_case(1, 2, 8, 4, 2, 32, 8, 3, (19, 7), (8, 8))
    q, kp, vp, kc, vc, tbl, hist, slen, kh, vh = case
    out = paged_chunk_attention(q, kp, vp, kc, vc, tbl, hist, slen,
                                window=window, interpret=True)
    want = ref.chunk_attention_ref(q, kh, vh, kc, vc, hist, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("s,r,h,kv,d,ps,mp,hists,slens", CHUNK_SHAPES)
def test_chunk_attention_fallback_matches_ref(s, r, h, kv, d, ps, mp,
                                              hists, slens):
    """The jnp gather/scatter fallback (the path CPU serving runs) against
    the same oracle — both backends share one contract."""
    from repro.models import layers as L
    case = _mk_chunk_case(2, s, r, h, kv, d, ps, mp, hists, slens)
    q, kp, vp, kc, vc, tbl, hist, slen, kh, vh = case
    out = L.paged_chunk_attention(q, kp, vp, kc, vc, tbl, hist, slen)
    want = ref.chunk_attention_ref(q, kh, vh, kc, vc, hist)
    for i in range(s):
        n = int(slen[i])
        np.testing.assert_allclose(
            np.asarray(out)[i, :n], np.asarray(want)[i, :n],
            atol=2e-5, rtol=2e-5, err_msg=f"segment {i}")


def test_chunk_attention_segment_isolation():
    """Perturbing one segment's history pages must not move any other
    segment's output (the packed verify dispatch mixes many requests)."""
    from repro.kernels.chunk_attention import paged_chunk_attention
    case = _mk_chunk_case(3, 3, 4, 4, 2, 32, 8, 3, (11, 8, 20), (4, 4, 4))
    q, kp, vp, kc, vc, tbl, hist, slen, _, _ = case
    base = np.asarray(paged_chunk_attention(q, kp, vp, kc, vc, tbl, hist,
                                            slen, interpret=True))
    victim_pages = np.asarray(tbl)[1]            # segment 1's pages
    kp2 = jnp.asarray(np.asarray(kp)).at[:, jnp.asarray(victim_pages)].set(7.0)
    out = np.asarray(paged_chunk_attention(q, kp2, vp, kc, vc, tbl, hist,
                                           slen, interpret=True))
    assert not np.allclose(base[1], out[1])      # victim did change
    np.testing.assert_array_equal(base[0], out[0])
    np.testing.assert_array_equal(base[2], out[2])
