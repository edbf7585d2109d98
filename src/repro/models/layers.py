"""Shared model-building primitives (pure JAX, no flax).

Parameters are declared via a *plan*: a pytree of ``ParamDef(shape, spec,
init)``. The same plan drives initialization (``init_from_plan``), sharding
(``utils.sharding.tree_specs``) and abstract eval (``abstract_params``), so
the three can never drift apart.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops


# --------------------------------------------------------------------------
# parameter plans
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class ParamDef:
    shape: Tuple[int, ...]
    spec: Optional[Tuple[Optional[str], ...]]       # logical axes
    init: str = "normal"                             # normal | zeros | ones
    std: float = 0.02


def _is_def(x) -> bool:
    return isinstance(x, ParamDef)


def _materialize(key, pd: ParamDef, dtype) -> jax.Array:
    if pd.init == "zeros":
        return jnp.zeros(pd.shape, dtype)
    if pd.init == "ones":
        return jnp.ones(pd.shape, dtype)
    return (pd.std * jax.random.normal(key, pd.shape, jnp.float32)).astype(dtype)


def init_from_plan(key, plan, dtype=jnp.float32):
    leaves, treedef = jax.tree.flatten(plan, is_leaf=_is_def)
    keys = jax.random.split(key, max(1, len(leaves)))
    return jax.tree.unflatten(
        treedef, [_materialize(k, pd, dtype) for k, pd in zip(keys, leaves)]
    )


def init_stacked(key, plan, n: int, dtype=jnp.float32):
    """Initialize ``n`` copies of ``plan`` stacked on a leading axis (for scan)."""
    keys = jax.random.split(key, n)
    per_layer = jax.vmap(lambda k: init_from_plan(k, plan, dtype))(keys)
    return per_layer


def stack_plan(plan, n: int):
    """The plan describing the stacked params (leading ``stack`` axis)."""
    return jax.tree.map(
        lambda pd: ParamDef((n,) + tuple(pd.shape), ("stack",) + tuple(pd.spec or (None,) * len(pd.shape)), pd.init, pd.std),
        plan,
        is_leaf=_is_def,
    )


def abstract_params(plan, dtype=jnp.float32):
    return jax.tree.map(
        lambda pd: jax.ShapeDtypeStruct(tuple(pd.shape), dtype), plan, is_leaf=_is_def
    )


# --------------------------------------------------------------------------
# norms
# --------------------------------------------------------------------------
def norm_plan(d: int, kind: str):
    if kind == "rmsnorm":
        return {"scale": ParamDef((d,), ("embed",), "ones")}
    if kind == "layernorm":
        return {"scale": ParamDef((d,), ("embed",), "ones"),
                "bias": ParamDef((d,), ("embed",), "zeros")}
    if kind == "layernorm_nonparam":
        return {}
    raise ValueError(kind)


def apply_norm(p, x, kind: str, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    if kind == "rmsnorm":
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
        return (y * p["scale"].astype(jnp.float32)).astype(x.dtype)
    mu = jnp.mean(xf, -1, keepdims=True)
    var = jnp.var(xf, -1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    if kind == "layernorm":
        y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return y.astype(x.dtype)


# --------------------------------------------------------------------------
# rotary embeddings
# --------------------------------------------------------------------------
def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, D); positions: broadcastable to (..., S)."""
    d = x.shape[-1]
    half = d // 2
    freq = 1.0 / (theta ** (np.arange(0, half, dtype=np.float32) * 2.0 / d))
    ang = positions[..., None].astype(jnp.float32) * freq          # (..., S, half)
    cos = jnp.cos(ang)[..., None, :]                                # (..., S, 1, half)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half: 2 * half]
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    out = jnp.concatenate([y1, y2, x[..., 2 * half:]], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# attention
# --------------------------------------------------------------------------
def repeat_kv(k: jax.Array, n_rep: int) -> jax.Array:
    if n_rep == 1:
        return k
    b, s, kv, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, kv, n_rep, d)).reshape(b, s, kv * n_rep, d)


def attention_dense(q, k, v, *, causal: bool, q_offset=0, bias_mask=None):
    """Plain quadratic attention. q:(B,Sq,H,D) k,v:(B,Sk,KV,D)."""
    b, sq, h, d = q.shape
    kv = k.shape[2]
    k = repeat_kv(k, h // kv)
    v = repeat_kv(v, h // kv)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) / np.sqrt(d)
    if causal:
        qpos = jnp.arange(sq)[:, None] + q_offset
        kpos = jnp.arange(k.shape[1])[None, :]
        scores = jnp.where(qpos >= kpos, scores, -1e30)
    if bias_mask is not None:
        scores = jnp.where(bias_mask, scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", w, v)


def attention_chunked(q, k, v, *, causal: bool = True, chunk_q: int = 1024,
                      chunk_k: int = 1024, window: int = 0):
    """Flash-style chunked attention in pure jnp (O(S·chunk) memory).

    Computes all (q-chunk × kv-chunk) tiles with masking — the Pallas TPU
    kernel (repro.kernels.flash_attention) skips fully-masked tiles; this jnp
    fallback trades ~2x attention FLOPs for static shapes under scan.
    """
    b, s, h, d = q.shape
    sk = k.shape[1]
    kvh = k.shape[2]
    n_rep = h // kvh
    cq = min(chunk_q, s)
    ck = min(chunk_k, sk)
    nq, nk = s // cq, sk // ck
    assert s % cq == 0 and sk % ck == 0, (s, sk, cq, ck)
    scale = 1.0 / np.sqrt(d)

    qc = q.reshape(b, nq, cq, h, d)
    kc = k.reshape(b, nk, ck, kvh, d)
    vc = v.reshape(b, nk, ck, kvh, d)

    def per_q_chunk(qi, qblk):
        # qblk: (b, cq, h, d)
        def inner(carry, xs):
            m, l, acc = carry
            ki, kblk, vblk = xs
            kblk = repeat_kv(kblk, n_rep)          # (b, ck, h, d)
            vblk = repeat_kv(vblk, n_rep)
            sc = jnp.einsum("bqhd,bkhd->bhqk", qblk, kblk).astype(jnp.float32) * scale
            qpos = qi * cq + jnp.arange(cq)[:, None]
            kpos = ki * ck + jnp.arange(ck)[None, :]
            mask = jnp.ones((cq, ck), bool)
            if causal:
                mask &= qpos >= kpos
            if window:
                mask &= qpos - kpos < window
            sc = jnp.where(mask, sc, -1e30)
            m_new = jnp.maximum(m, sc.max(-1))
            p = jnp.exp(sc - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", p.astype(qblk.dtype), vblk
            ).astype(jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, h, cq), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((b, h, cq), jnp.float32)
        a0 = jnp.zeros((b, h, cq, d), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            inner, (m0, l0, a0), (jnp.arange(nk), kc.swapaxes(0, 1), vc.swapaxes(0, 1))
        )
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return out.swapaxes(1, 2).astype(q.dtype)   # (b, cq, h, d)

    outs = jax.lax.map(lambda xs: per_q_chunk(xs[0], xs[1]),
                       (jnp.arange(nq), qc.swapaxes(0, 1)))
    return outs.swapaxes(0, 1).reshape(b, s, h, d)


def big_attention(q, k, v, *, causal: bool, window: int = 0):
    """Dispatch: Pallas flash kernel on real TPUs; flash-with-custom-VJP
    (O(S) residuals, tile recomputation in backward) elsewhere."""
    s, sk = q.shape[1], k.shape[1]
    backend = ops.default_backend()
    if backend != "jnp" and s % 512 == 0 and sk % 512 == 0:
        return ops.flash_attention(q, k, v, causal=causal, window=window,
                                   backend=backend)
    if max(s, sk) > 1024:
        from repro.kernels.flash_vjp import flash_attention_vjp
        cq = 512 if s % 512 == 0 else s
        ck = 512 if sk % 512 == 0 else sk
        return flash_attention_vjp(q, k, v, causal=causal, window=window,
                                   chunk_q=cq, chunk_k=ck)
    if window:
        qp = jnp.arange(s)[:, None]
        kp = jnp.arange(sk)[None, :]
        mask = (qp - kp < window) & ((qp >= kp) if causal else True)
        return attention_dense(q, k, v, causal=False, bias_mask=mask)
    return attention_dense(q, k, v, causal=causal)


# --------------------------------------------------------------------------
# packed ragged prefill
# --------------------------------------------------------------------------
def packed_positions(seg_ids, seg_starts):
    """Within-segment position of every token in a packed row.

    seg_ids: (T,) int32 non-decreasing segment id per token (padding
    tokens carry id == S, one past the last real segment); seg_starts:
    (S,) int32 packed offset of each segment's first token. Padding
    tokens get position 0 (their rope/pos-embed values are never read —
    attention masks them and their outputs are discarded)."""
    t = jnp.arange(seg_ids.shape[0], dtype=jnp.int32)
    s = seg_starts.shape[0]
    start = seg_starts[jnp.minimum(seg_ids, s - 1)]
    return jnp.where(seg_ids < s, t - start, 0)


def segments_to_rows(x, seg_starts, seg_lens, row_len):
    """Gather a packed (T, ...) tensor into per-segment rows
    (S, row_len, ...): row i holds its segment's tokens at columns
    0..len_i-1 and exact zeros after — the layout a per-request prefill
    would see. Segments are CONTIGUOUS in the packed row, so this is a
    masked gather (start + column), not a scatter — measurably cheaper on
    the CPU fallback and trivially parallel. Together with
    ``rows_to_segments`` this bridges the packed layout (where the
    O(tokens) ops run) and the per-segment row layout the sequence-mixing
    fallbacks (dense attention, conv, SSD scan) need."""
    t = x.shape[0]
    idx = seg_starts[:, None] + jnp.arange(row_len, dtype=jnp.int32)[None, :]
    rows = x[jnp.clip(idx, 0, t - 1)]                  # (S, row_len, ...)
    valid = jnp.arange(row_len)[None, :] < seg_lens[:, None]
    return jnp.where(valid.reshape(valid.shape + (1,) * (x.ndim - 1)),
                     rows, 0)


def rows_to_segments(rows, seg_ids, positions):
    """Gather per-segment rows back to the packed (T, ...) layout — the
    inverse of ``segments_to_rows`` for real tokens. Padding tokens (a
    clamped row/column) read garbage that every consumer discards: their
    K/V lands on the null page, their activations feed no segment's last
    logits."""
    r = jnp.clip(seg_ids, 0, rows.shape[0] - 1)
    c = jnp.clip(positions, 0, rows.shape[1] - 1)
    return rows[r, c]


def packed_prefill_attention(q, k, v, seg_ids, positions, seg_starts,
                             seg_lens, *, row_len: int, window: int = 0):
    """Segment-blocked causal self-attention over a packed token row.

    q: (1, T, H, D); k/v: (1, T, KV, D); seg_ids/positions: (T,);
    seg_starts/seg_lens: (S,). Token i attends to token j iff
    seg_ids[i] == seg_ids[j] and j <= i.

    On real TPUs this dispatches to the segment flash kernel
    (repro.kernels.flash_attention.segment_flash_attention), whose
    scalar-prefetched segment boundaries skip fully cross-segment tiles —
    the packed row pays for its actual token pairs. The fallback gathers
    each segment into its own row (q/k/v in ONE fused gather along the
    head axis) and runs the SAME ``attention_dense`` body the padded
    prefill path runs — same key set, same reduction order, exact-zero
    padding terms — so packed and padded prefill greedy outputs agree
    bit-for-bit on CPU; its attention FLOPs match pad-to-``row_len``
    while every other prefill op runs on sum(lens) tokens instead of
    batch × max."""
    b, t, h, d = q.shape
    kvh = k.shape[2]
    # half-step buckets (3·2^k) are 256-multiples, not 512-multiples —
    # drop to 256-wide tiles there so the kernel stays in play for every
    # packed bucket >= 512
    backend = ops.default_backend()
    if backend != "jnp" and t >= 512 and t % 256 == 0:
        blk = 512 if t % 512 == 0 else 256
        return ops.segment_flash_attention(q, k, v, seg_ids, window=window,
                                           block_q=blk, block_k=blk,
                                           backend=backend)
    qkv = jnp.concatenate([q[0], k[0], v[0]], axis=1)   # (T, H+2KV, D)
    rows = segments_to_rows(qkv, seg_starts, seg_lens, row_len)
    qr, kr, vr = rows[:, :, :h], rows[:, :, h:h + kvh], rows[:, :, h + kvh:]
    # big_attention applies the SAME dispatch rule the padded prefill path
    # uses (dense under 1024, chunked flash above — no materialized
    # (S, H, row, row) scores for long rows, and bit-parity with padded
    # prefill holds whenever both land on the same side of that rule)
    ar = big_attention(qr, kr, vr, causal=True, window=window)
    return rows_to_segments(ar, seg_ids, positions)[None]


def packed_cross_attention(q, k_cross, v_cross, seg_ids, positions,
                           seg_starts, seg_lens, *, row_len: int):
    """Per-segment cross-attention for packed encoder-decoder prefill.

    q: (1, T, H, D) packed decoder queries; k_cross/v_cross:
    (S, enc_seq, KV, D) — one read-only encoder block per segment. Each
    packed token attends its OWN segment's encoder output: queries are
    gathered to per-segment rows, run through the same dense non-causal
    attention the padded path uses, and gathered back."""
    qr = segments_to_rows(q[0], seg_starts, seg_lens, row_len)
    ar = attention_dense(qr, k_cross, v_cross, causal=False)
    return rows_to_segments(ar, seg_ids, positions)[None]


def cache_row_update(buf, new, slot):
    """Write ``new`` (B, 1, ...) into ``buf`` (B, C, ...) at per-row ring
    position ``slot`` (B,) along axis 1.

    Implemented as a batched scatter (``.at[b, slot_b]``), which touches one
    row per sequence; inside the decode layer-scan the buffer is a carry, so
    XLA applies it in place. The one-hot-select alternative rewrites the
    whole cache every layer — measured 1.5x slower per decode step at
    C=128 on CPU, and O(cache) instead of O(row) HBM traffic at real
    cache lengths."""
    bidx = jnp.arange(buf.shape[0])
    return buf.at[bidx, slot].set(new[:, 0])


def paged_cache_update(buf, new, layer, pages, slots):
    """Write ``new`` (B, 1, KV, D) into layer ``layer`` of a stacked
    head-major paged pool ``buf`` (L, KV, P, page_size, D) at per-row
    physical page ``pages`` (B,) and in-page offset ``slots`` (B,).

    The write is a scatter of B x KV separate (D,) rows, each at
    (layer, head, page, slot): the scatter's window is the head dim alone,
    which is already minor in the pool's row-major layout, so the compiler
    keeps the pool where it is and scatters in place. Do not write it as
    ``buf[layer].at[:, pages, slots]``: a (KV, D) window makes the compiler
    copy the layer out into a layout that puts both dims minor and back
    again (four whole-layer copies per layer and pool in a decode step).

    Live rows own disjoint pages so the scatter rows never collide; vacant
    rows all target the reserved null page 0 at offset 0 — duplicate
    indices there are harmless because the null page is never read (see
    ``repro.serving.kv_cache``)."""
    heads = jnp.arange(buf.shape[1])[None, :]
    return buf.at[layer, heads, pages[:, None], slots[:, None]].set(
        new[:, 0])


def gather_pages(pool, block_tables):
    """The jnp fallback's view of a head-major pool (KV, P, page_size, D)
    through (B, max_pages) block tables: each row's pages in logical order
    as a contiguous (B, max_pages * page_size, KV, D) cache."""
    kvh, _, page_size, d = pool.shape
    b, max_pages = block_tables.shape
    rows = pool[:, block_tables]               # (KV, B, max_pages, ps, D)
    return rows.transpose(1, 2, 3, 0, 4).reshape(
        b, max_pages * page_size, kvh, d)


def _layer_pool(pool, layer):
    """One layer of a stacked (L, KV, P, page_size, D) pool, or ``pool``
    itself where ``layer`` is None (a single layer's pool)."""
    return pool if layer is None else pool[layer]


def _paged_backend(page_size: int) -> str:
    """``ops.default_backend()`` for a paged cache. Where the Pallas
    kernels run, they alone read the pages, and their (page_size, D) page
    tile must be sublane aligned: a misaligned page is refused, not served
    through the jnp gather, which reads every page a row's table spans and
    would hide that the kernel never ran."""
    backend = ops.default_backend()
    if backend != "jnp" and page_size % 8:
        raise ValueError(
            f"page_size {page_size} is not a multiple of 8: the paged "
            "Pallas kernels cannot tile it")
    return backend


def _masked_decode_attention(q, k_cache, v_cache, lengths):
    """The jnp (CPU/dry-run) decode-attention body: masked full-cache
    compute with static shapes. Shared verbatim by the contiguous and the
    paged (post-gather) paths so ring and paged greedy decode stay
    bit-exact on the fallback backend."""
    b, c, kvh, d = k_cache.shape
    h = q.shape[1]
    qg = q.reshape(b, kvh, h // kvh, d)
    # preferred_element_type keeps the cache operands bf16 (no hoisted
    # full-cache f32 convert) while accumulating scores in f32
    sc = jnp.einsum("bgrd,bkgd->bgrk", qg, k_cache,
                    preferred_element_type=jnp.float32)
    sc = sc / np.sqrt(d)
    mask = jnp.arange(c)[None, None, None, :] < lengths[:, None, None, None]
    sc = jnp.where(mask, sc, -1e30)
    w = jax.nn.softmax(sc, axis=-1).astype(q.dtype)
    out = jnp.einsum("bgrk,bkgd->bgrd", w, v_cache,
                     preferred_element_type=jnp.float32)
    out = jnp.where(lengths[:, None, None, None] > 0, out, 0.0)
    return out.reshape(b, h, d).astype(q.dtype)


def paged_decode_attention(q, k_pages, v_pages, block_tables, valid_len,
                           layer=None):
    """Single-token attention over a block-table paged KV cache.

    q: (B, H, D); k_pages/v_pages: (L, KV, P, page_size, D) shared physical
    head-major pools, stacked over layers and read at ``layer`` (or one
    layer's (KV, P, page_size, D) pools with ``layer`` None);
    block_tables: (B, max_pages) int32 page ids; valid_len: scalar or
    per-sequence (B,) int32 lengths. Rows with length 0 (vacant slots,
    table rows parked on the null page) return zeros.

    On real TPUs this dispatches to the paged Pallas kernel
    (repro.kernels.paged_attention): the block table rides in via scalar
    prefetch and pages the index maps directly, so HBM traffic scales with
    each row's actual length. The fallback gathers each row's pages into
    logical order and reuses the exact masked-decode body of the
    contiguous path — bit-identical to ring decode for equal contents.
    """
    b = q.shape[0]
    lengths = jnp.broadcast_to(
        jnp.asarray(valid_len, jnp.int32).reshape(-1), (b,))
    backend = _paged_backend(k_pages.shape[-2])
    if backend != "jnp":
        from repro.kernels.paged_attention import \
            paged_decode_attention as _pallas
        return _pallas(q, k_pages, v_pages, block_tables, lengths, layer,
                       interpret=backend == "interpret")
    # jnp gather fallback: pages -> logical (B, C, KV, D) view
    kc = gather_pages(_layer_pool(k_pages, layer), block_tables)
    vc = gather_pages(_layer_pool(v_pages, layer), block_tables)
    return _masked_decode_attention(q, kc, vc, lengths)


def _masked_chunk_attention(q_rows, k_cache, v_cache, lengths):
    """The jnp (CPU/dry-run) incremental chunk-attention body: each of the
    R chunk queries runs the EXACT single-token masked-decode body against
    the (virtual) per-segment cache with its own valid length — so a
    verify chunk's logits are bit-identical to the decode steps it
    replaces on the fallback backend (the property speculative decoding's
    bit-exactness rests on).

    q_rows: (B, R, H, D); caches: (B, C, KV, D) with the chunk's own K/V
    already scattered in at positions [hist, hist + R); lengths: (B, R)
    int32 — query r attends cache entries [0, lengths[b, r]), and rows
    with length 0 (padding) return zeros."""

    def per_pos(args):
        q, ln = args
        return _masked_decode_attention(q, k_cache, v_cache, ln)

    out = jax.lax.map(per_pos, (q_rows.swapaxes(0, 1), lengths.T))
    return out.swapaxes(0, 1)


def paged_chunk_attention(q_rows, k_pages, v_pages, k_rows, v_rows,
                          block_tables, hist_lens, seg_lens, layer=None):
    """Incremental chunk attention: R new tokens per segment attend the
    K/V their sequence already wrote into the shared page pool plus the
    chunk's own K/V causally — the continuation/verification sibling of
    ``paged_decode_attention``.

    q_rows/k_rows/v_rows: (S, R, H|KV, D) per-segment chunk rows (row r
    of segment s sits at absolute position hist_lens[s] + r);
    k_pages/v_pages: (L, KV, P, page_size, D) stacked pools read at
    ``layer`` (or one layer's (KV, P, page_size, D) with ``layer`` None);
    block_tables: (S, max_pages) int32; hist_lens/seg_lens: (S,) int32.
    Rows r >= seg_lens[s] are padding (zeros in, garbage out — callers
    discard them).

    On real TPUs this dispatches to the chunked paged Pallas kernel
    (repro.kernels.chunk_attention); the fallback gathers each segment's
    pages into logical order, scatters the chunk rows in at their
    absolute positions, and runs the exact masked-decode body per chunk
    position — bit-identical to the decode steps the chunk replaces."""
    s, r_len, h, d = q_rows.shape
    hist = jnp.broadcast_to(
        jnp.asarray(hist_lens, jnp.int32).reshape(-1), (s,))
    slen = jnp.broadcast_to(
        jnp.asarray(seg_lens, jnp.int32).reshape(-1), (s,))
    backend = _paged_backend(k_pages.shape[-2])
    if backend != "jnp":
        from repro.kernels.chunk_attention import \
            paged_chunk_attention as _pallas
        return _pallas(q_rows, k_pages, v_pages, k_rows, v_rows,
                       block_tables, hist, slen, layer,
                       interpret=backend == "interpret")
    kc = gather_pages(_layer_pool(k_pages, layer), block_tables)
    vc = gather_pages(_layer_pool(v_pages, layer), block_tables)
    pos = hist[:, None] + jnp.arange(r_len, dtype=jnp.int32)[None, :]
    sidx = jnp.arange(s)[:, None]
    kc = kc.at[sidx, pos].set(k_rows, mode="drop")
    vc = vc.at[sidx, pos].set(v_rows, mode="drop")
    lengths = jnp.where(
        jnp.arange(r_len, dtype=jnp.int32)[None, :] < slen[:, None],
        pos + 1, 0)
    return _masked_chunk_attention(q_rows, kc, vc, lengths)


def decode_index(pos, cache, key):
    """Per-row write/read machinery for one decode step over EITHER cache
    layout — the single place the paged-vs-ring storage contract lives, so
    the three attention families cannot drift (the layout is a static
    pytree property: ``block_tables`` present = paged).

    pos: (B,) int32 current positions; ``key``: the K leaf the layout is
    read from. Returns ``update_attend(q, k, v, kbuf, vbuf, layer,
    window=0)``, which writes the step's (B, 1, KV, D) entries ``k``/``v``
    at each row's coordinates in layer ``layer`` of the stacked K/V leaves
    ``kbuf``/``vbuf``, runs decode attention of ``q`` against that layer,
    and returns ``(out, kbuf, vbuf)``.

    A paged pool (L, KV, P, page_size, D) is never sliced: the write is a
    scatter of (D,) rows at (layer, head, page, slot)
    (``paged_cache_update``) and the kernel picks the layer in its index
    maps, so the pool keeps one row-major layout and the step copies none
    of it. A ring (L, B, C, KV, D) slices its layer out, writes and reads
    the slice, and updates it back into the stack."""
    if "block_tables" in cache:
        tables = cache["block_tables"]
        page_size = cache[key].shape[3]
        bidx = jnp.arange(pos.shape[0])
        # past-capacity clamp is belt-and-braces: the engine caps every
        # slot's token budget at its page capacity, so live rows never
        # reach it (vacant rows sit at pos 0 on the null page)
        page = tables[bidx, jnp.minimum(pos // page_size,
                                        tables.shape[1] - 1)]
        slot = pos % page_size
        valid = jnp.minimum(pos + 1, tables.shape[1] * page_size)

        def update_attend(q, k, v, kbuf, vbuf, layer, window: int = 0):
            if window:
                # a paged slot retains FULL history (pages never evict),
                # so windowed attention needs page-level masking that is
                # not implemented — the engine keeps windowed configs on
                # ring slots, whose overwrite IS the window. Loud > wrong.
                raise NotImplementedError(
                    "sliding-window attention over a paged cache")
            kbuf = paged_cache_update(kbuf, k, layer, page, slot)
            vbuf = paged_cache_update(vbuf, v, layer, page, slot)
            out = paged_decode_attention(q, kbuf, vbuf, tables, valid, layer)
            return out, kbuf, vbuf

        return update_attend

    cache_len = cache[key].shape[2]
    slot = (pos % cache_len) if cache_len > 0 else jnp.zeros_like(pos)
    valid = jnp.minimum(pos + 1, cache_len)

    def update_attend(q, k, v, kbuf, vbuf, layer, window: int = 0):
        kc = cache_row_update(
            jax.lax.dynamic_index_in_dim(kbuf, layer, keepdims=False), k,
            slot)
        vc = cache_row_update(
            jax.lax.dynamic_index_in_dim(vbuf, layer, keepdims=False), v,
            slot)
        out = decode_attention(q, kc, vc, valid, window=window)
        kbuf = jax.lax.dynamic_update_index_in_dim(kbuf, kc, layer, 0)
        vbuf = jax.lax.dynamic_update_index_in_dim(vbuf, vc, layer, 0)
        return out, kbuf, vbuf

    return update_attend


def carry_cache_meta(out, cache):
    """Thread the storage-contract leaves a decode step only reads
    (``block_tables``) from the old cache into the new one, preserving the
    pytree structure the donated input had — the other half of the
    contract ``decode_index`` owns, so model families never hand-write
    paged-vs-ring knowledge."""
    if "block_tables" in cache:
        out["block_tables"] = cache["block_tables"]
    return out


def decode_attention(q, k_cache, v_cache, valid_len, *, window: int = 0,
                     ring_pos=None):
    """Single-token attention over a KV cache.

    q: (B, H, D); k_cache/v_cache: (B, C, KV, D); valid_len: scalar int or
    per-sequence (B,) int32 lengths — number of valid cache entries per row,
    so mixed-length batches don't pay for the longest sequence. For
    ring-buffer (sliding-window) caches the whole buffer is valid once full;
    masking handles the partial-fill phase. Rows with length 0 (vacant
    continuous-batching slots) return zeros.

    On real TPUs this dispatches to the ragged Pallas kernel
    (repro.kernels.decode_attention), whose per-row cache-block skip makes
    HBM traffic scale with each row's actual length. The jnp path below is
    the CPU/dry-run fallback: masked full-cache compute with static shapes.

    GQA is computed as a grouped einsum — NOT a materialized repeat_kv.
    A repeat broadcasts the whole cache to H heads, which under SPMD turns
    a sequence-sharded cache into a full all-gather per layer (measured:
    25.8 GB/layer on yi-9b decode_32k).
    """
    b, c, kvh, d = k_cache.shape
    lengths = jnp.broadcast_to(
        jnp.asarray(valid_len, jnp.int32).reshape(-1), (b,))
    backend = ops.default_backend()
    if backend != "jnp" and c % 128 == 0:
        from repro.kernels.decode_attention import decode_attention as _pallas
        return _pallas(q, k_cache, v_cache, lengths,
                       interpret=backend == "interpret")
    return _masked_decode_attention(q, k_cache, v_cache, lengths)


# --------------------------------------------------------------------------
# GQA attention block (params + apply)
# --------------------------------------------------------------------------
def attn_plan(cfg) -> dict:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    h, kv = cfg.num_heads, cfg.num_kv_heads
    # NOTE: head_dim is deliberately NOT a fallback shard axis here — a
    # head_dim-sharded q/k makes every attention score tile a partial-sum
    # all-reduce (measured: qwen2 prefill_32k went collective-dominated,
    # ~2.9 TB/device of tile ARs). Non-divisible head counts replicate the
    # (small) projection weights; the KV cache memory is handled by
    # sequence-sharding instead (see cache plans).
    p = {
        "wq": ParamDef((d, h, hd), ("embed", "heads", None)),
        "wk": ParamDef((d, kv, hd), ("embed", "kv_heads", None)),
        "wv": ParamDef((d, kv, hd), ("embed", "kv_heads", None)),
        "wo": ParamDef((h, hd, d), ("heads", None, "embed")),
    }
    if cfg.qkv_bias:
        p["bq"] = ParamDef((h, hd), ("heads", None), "zeros")
        p["bk"] = ParamDef((kv, hd), ("kv_heads", None), "zeros")
        p["bv"] = ParamDef((kv, hd), ("kv_heads", None), "zeros")
    return p


def constrain_q_prefill(cfg, q, tp: int = 16):
    """Context parallelism for archs whose q-head count doesn't divide the
    TP width (qwen2: 14, whisper: 12, granite: 24): shard the q SEQUENCE so
    attention compute splits tp-ways with only a tiny all-gather of the
    (GQA-small) k/v — instead of replicating the whole S² computation."""
    if cfg.num_heads % tp:
        from repro.utils.sharding import maybe_constrain
        return maybe_constrain(q, "batch", "kv_seq", None, None)
    return q


def cp_attention(cfg, q, k, v, *, causal: bool, window: int = 0):
    """Context-parallel self-attention for replicated-head architectures.

    Sharding constraints alone do NOT make XLA partition the chunked
    attention's lax.map/scan over the sequence (measured: qwen2 prefill
    attention stayed 16x-replicated). This dispatcher makes the split
    explicit with shard_map: each TP shard runs flash attention on its
    sequence slice of q against the (small, GQA) full k/v, with the causal
    mask shifted by the shard's offset.
    """
    from repro.utils.sharding import active_mesh, batch_axes, resolve_spec
    mesh = active_mesh()
    s = q.shape[1]
    if (mesh is None or "model" not in mesh.axis_names
            or cfg.num_heads % mesh.shape["model"] == 0
            or s % (mesh.shape["model"] * 512) != 0
            or q.shape[0] % max(1, np.prod([mesh.shape[a]
                                            for a in batch_axes(mesh)])) != 0):
        q = constrain_q_prefill(cfg, q)
        return big_attention(q, k, v, causal=causal, window=window)

    from jax.sharding import PartitionSpec as P
    from repro.kernels.flash_vjp import flash_attention_vjp
    ba = batch_axes(mesh)
    bspec = ba if len(ba) > 1 else (ba[0] if ba else None)
    q_spec = P(bspec, "model")
    kv_spec = P(bspec)
    local_s = s // mesh.shape["model"]

    def local(q_l, k_l, v_l):
        off = (jax.lax.axis_index("model") * local_s).astype(jnp.float32)
        return flash_attention_vjp(q_l, k_l, v_l, causal=causal,
                                   window=window, q_offset=off)

    return jax.shard_map(local, mesh=mesh,
                         in_specs=(q_spec, kv_spec, kv_spec),
                         out_specs=q_spec, check_vma=False)(q, k, v)


def constrain_q_decode(cfg, q, tp: int = 16):
    """Against a sequence-sharded cache (kv heads non-divisible), the
    single-token q must be replicated across the TP group: scores are then
    computed per cache shard and combined by a (batch, heads)-sized
    distributed softmax — bytes, not gigabytes, of all-reduce."""
    if cfg.num_kv_heads % tp:
        from repro.utils.sharding import maybe_constrain
        return maybe_constrain(q, "batch", None, None)
    return q


def kv_cache_spec(cfg, tp: int = 16):
    """Sharding for a (layers, batch, seq, kv_heads, head_dim) cache.

    KV heads shard when they divide the TP width (zero-communication local
    decode attention); otherwise the *sequence* dim shards — decode
    attention then does a distributed softmax whose all-reduce is only
    (batch, heads[, head_dim]) per layer, thousands of times smaller than
    head_dim-sharded partial sums."""
    if cfg.num_kv_heads and cfg.num_kv_heads % tp == 0:
        return ("stack", "batch", None, "kv_heads", None)
    return ("stack", "batch", "kv_seq", None, None)


def paged_kv_cache_spec(cfg, tp: int = 16):
    """Sharding for a (layers, kv_heads, num_pages, page_size, head_dim)
    paged pool. KV heads shard when they divide the TP width (same local
    decode-attention argument as ``kv_cache_spec``); otherwise the *page*
    dim shards — pages are the paged analogue of the sequence dim, and the
    block table (host-replicated int32) stays tiny either way."""
    if cfg.num_kv_heads and cfg.num_kv_heads % tp == 0:
        return ("stack", "kv_heads", None, None, None)
    return ("stack", None, "kv_seq", None, None)


def paged_kv_plan(cfg, n_layers: int, num_pages: int, page_size: int):
    """The one paged K/V pool layout every family uses: HEAD-MAJOR
    (layers, KV, P, page_size, D), so one page of one kv head is the
    (page_size, D) tile the paged Pallas kernels stream (the layout of
    the upstream TPU paged-attention kernel)."""
    return ParamDef((n_layers, cfg.num_kv_heads, num_pages, page_size,
                     cfg.resolved_head_dim), paged_kv_cache_spec(cfg),
                    "zeros")


def attn_qkv(p, cfg, x, positions):
    """Project + rope. x: (B,S,d) -> q:(B,S,H,hd), k,v:(B,S,KV,hd)."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(x.dtype))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    if not cfg.learned_pos_emb:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def attn_out(p, x_dtype, attn):
    """attn: (B,S,H,hd) or (B,H,hd) -> project back to d_model."""
    return jnp.einsum("...hk,hkd->...d", attn, p["wo"].astype(x_dtype))


# --------------------------------------------------------------------------
# SwiGLU MLP
# --------------------------------------------------------------------------
def mlp_plan(cfg, d_ff: Optional[int] = None) -> dict:
    d, ff = cfg.d_model, d_ff or cfg.d_ff
    return {
        "wi_gate": ParamDef((d, ff), ("embed", "mlp")),
        "wi_up": ParamDef((d, ff), ("embed", "mlp")),
        "wo": ParamDef((ff, d), ("mlp", "embed")),
    }


def apply_mlp(p, x):
    g = jnp.einsum("...d,df->...f", x, p["wi_gate"].astype(x.dtype))
    u = jnp.einsum("...d,df->...f", x, p["wi_up"].astype(x.dtype))
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    return jnp.einsum("...f,fd->...d", h, p["wo"].astype(x.dtype))


# --------------------------------------------------------------------------
# sampling
# --------------------------------------------------------------------------
def top_k_top_p_filter(logits: jax.Array, *, top_k: int = 0,
                       top_p: float = 1.0) -> jax.Array:
    """Mask logits outside the top-k set and/or the top-p nucleus to -1e30.

    ``top_k``/``top_p`` are static Python values, so this is jit-safe inside
    the decode scan body — each (top_k, top_p) pair is one executable, not a
    per-step branch. The arg-max token is always kept, so a degenerate
    ``top_p`` can never mask the whole vocabulary."""
    if top_k and top_k < logits.shape[-1]:
        kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, -1e30, logits)
    if top_p < 1.0:
        srt = jnp.sort(logits, axis=-1)[..., ::-1]          # descending
        probs = jax.nn.softmax(srt.astype(jnp.float32), axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # keep tokens whose cumulative mass BEFORE them is < top_p
        keep = (cum - probs) < top_p
        keep = keep.at[..., 0].set(True)
        thresh = jnp.min(jnp.where(keep, srt, jnp.inf), axis=-1,
                         keepdims=True).astype(logits.dtype)
        logits = jnp.where(logits < thresh, -1e30, logits)
    return logits


def sample_logits(rng: jax.Array, logits: jax.Array, *,
                  temperature: float = 1.0, top_k: int = 0,
                  top_p: float = 1.0) -> jax.Array:
    """Draw next tokens (B,) int32 from (B, V) logits.

    temperature <= 0 degenerates to greedy arg-max (bit-exact with the
    greedy decode path); otherwise temperature-scaled top-k/top-p
    (nucleus) sampling via Gumbel trick (``jax.random.categorical``)."""
    if temperature <= 0.0:
        return jnp.argmax(logits, -1).astype(jnp.int32)
    lg = logits.astype(jnp.float32) / temperature
    lg = top_k_top_p_filter(lg, top_k=top_k, top_p=top_p)
    return jax.random.categorical(rng, lg).astype(jnp.int32)


# --------------------------------------------------------------------------
# embeddings / unembedding
# --------------------------------------------------------------------------
def embed_plan(cfg) -> dict:
    v = cfg.padded_vocab
    p = {"embedding": ParamDef((v, cfg.d_model), ("vocab", "embed"))}
    if not cfg.tie_embeddings:
        p["lm_head"] = ParamDef((cfg.d_model, v), ("embed", "vocab"))
    return p


def embed_tokens(p, tokens, dtype):
    return p["embedding"].astype(dtype)[tokens]


def unembed(p, x, cfg):
    """Logits over the PADDED vocab; pad rows masked to -inf (sampling and
    cross-entropy both ignore them; slicing back would break the vocab
    sharding)."""
    w = p.get("lm_head")
    if w is None:
        w = p["embedding"].T
    logits = jnp.einsum("...d,dv->...v", x, w.astype(x.dtype))
    if cfg.padded_vocab != cfg.vocab_size:
        pad_mask = jnp.arange(cfg.padded_vocab) >= cfg.vocab_size
        logits = jnp.where(pad_mask, jnp.asarray(-1e9, logits.dtype), logits)
    return logits
