"""Flash attention as a Pallas TPU kernel.

TPU-native design (not a CUDA port): the grid is (batch, q_heads, q_blocks,
kv_blocks) with the kv_blocks dimension innermost ("arbitrary" semantics —
sequential revisits of the same output tile); running max / denominator /
accumulator live in VMEM scratch so the softmax is computed online without
ever materializing the (S, S) score matrix in HBM. Tile shapes are chosen so
q·kᵀ hits the MXU with lane-aligned (multiple-of-128) contractions.

Fully-masked tiles (future tiles under causality, expired tiles under a
sliding window) are *skipped* via ``pl.when`` — this is the part the
chunked-jnp fallback cannot do with static shapes, and is worth ~2× on
causal prefill.

GQA is native: the kv-head block index is derived as ``h * KV // H``.

The wrappers take the models' (B, S, H, D) activations and hand the kernel
head-major (B, H, S, D) views, so each block is a (rows, D) tile: Mosaic
lowers a block only when its last two dims are whole or (8, 128) aligned,
and a head axis blocked at 1 in the second-to-last position is neither.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, window: int,
                  block_q: int, block_k: int, nk: int):
    i = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = i * block_q
    k_start = j * block_k

    should_run = jnp.bool_(True)
    if causal:
        should_run &= k_start <= q_start + block_q - 1
    if window:
        # tile fully expired if even the newest key is outside the window
        should_run &= (q_start - (k_start + block_k - 1)) < window

    @pl.when(should_run)
    def _compute():
        q = q_ref[...].astype(jnp.float32)
        k = k_ref[...].astype(jnp.float32)
        v = v_ref[...].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = jnp.ones((block_q, block_k), jnp.bool_)
        if causal:
            mask &= qpos >= kpos
        if window:
            mask &= (qpos - kpos) < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    # last kv tile this q tile will ever see
    if causal:
        last_j = jnp.minimum(nk - 1, (q_start + block_q - 1) // block_k)
    else:
        last_j = nk - 1

    @pl.when(j == last_j)
    def _finalize():
        out = acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
        o_ref[...] = out.astype(o_ref.dtype)


def _segment_flash_kernel(qfirst_smem, klast_smem, q_ref, k_ref, v_ref,
                          qseg_ref, kseg_ref, o_ref, m_scr, l_scr, acc_scr, *,
                          scale: float, window: int, block_q: int,
                          block_k: int, nq: int, nk: int):
    """Packed-prefill flash body: same online softmax as ``_flash_kernel``
    plus a segment-equality mask, with fully cross-segment tiles skipped.

    ``qfirst_smem``/``klast_smem`` are the scalar-prefetched segment ids
    of each q tile's FIRST token and each kv tile's LAST token — the
    segment *boundaries* read at tile granularity (the same trick the
    paged decode kernel plays with its block table): because ids are
    non-decreasing along the packed row, a kv tile whose last id is below
    the q tile's first id lies entirely in earlier segments and is skipped
    wholesale via ``pl.when``. ``qseg_ref`` (a (block_q, 1) column) and
    ``kseg_ref`` (a (1, block_k) row) are the per-token ids as VMEM tiles
    for the per-element mask inside surviving tiles."""
    bi = pl.program_id(0)
    i = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = i * block_q
    k_start = j * block_k

    # causal skip (future tiles) + segment skip (tiles wholly in earlier
    # segments: max kv-tile id < min q-tile id)
    should_run = k_start <= q_start + block_q - 1
    should_run &= klast_smem[bi * nk + j] >= qfirst_smem[bi * nq + i]
    if window:
        should_run &= (q_start - (k_start + block_k - 1)) < window

    @pl.when(should_run)
    def _compute():
        q = q_ref[...].astype(jnp.float32)
        k = k_ref[...].astype(jnp.float32)
        v = v_ref[...].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = (qseg_ref[...] == kseg_ref[...]) & (qpos >= kpos)
        if window:
            mask &= (qpos - kpos) < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    # the diagonal tile always runs (a token attends at least to itself,
    # and its kv tile's last id >= its own id), so finalizing there is safe
    last_j = jnp.minimum(nk - 1, (q_start + block_q - 1) // block_k)

    @pl.when(j == last_j)
    def _finalize():
        out = acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
        o_ref[...] = out.astype(o_ref.dtype)


def _head_major(x):
    return x.transpose(0, 2, 1, 3)                 # (B,S,H,D) <-> (B,H,S,D)


def segment_flash_attention(q, k, v, seg_ids, *, window: int = 0,
                            block_q: int = 512, block_k: int = 512,
                            interpret: bool = False):
    """Segment-masked causal flash attention for packed ragged prefill.

    q: (B,T,H,D); k,v: (B,T,KV,D); seg_ids: (T,) or (B,T) non-decreasing
    int32 segment ids (padding tokens carry an id no real token shares).
    Token i attends to token j iff their ids match and j <= i. Tiles that
    lie entirely in earlier segments are skipped via the scalar-prefetched
    boundary test — packed mixed-length batches pay for their actual
    token pairs, not the (sum of lengths)² rectangle."""
    b, t, h, d = q.shape
    kvh = k.shape[2]
    block_q = min(block_q, t)
    block_k = min(block_k, t)
    assert t % block_q == 0 and t % block_k == 0, (t, block_q, block_k)
    nq, nk = t // block_q, t // block_k
    scale = 1.0 / np.sqrt(d)
    seg = jnp.asarray(seg_ids, jnp.int32)
    seg = jnp.broadcast_to(seg.reshape(-1, t) if seg.ndim > 1
                           else seg[None, :], (b, t))
    # tile boundaries for the scalar skip test, flattened 1-D (SMEM pads
    # 2-D arrays), and the per-token ids as a column and a row for the mask
    q_first = seg[:, ::block_q].reshape(-1)
    k_last = seg[:, block_k - 1::block_k].reshape(-1)

    kernel = functools.partial(
        _segment_flash_kernel, scale=scale, window=window,
        block_q=block_q, block_k=block_k, nq=nq, nk=nk)

    def kv_map(b_, h_, i, j, *_):
        return (b_, h_ * kvh // h, j, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((None, None, block_q, d),
                         lambda b_, h_, i, j, *_: (b_, h_, i, 0)),
            pl.BlockSpec((None, None, block_k, d), kv_map),
            pl.BlockSpec((None, None, block_k, d), kv_map),
            pl.BlockSpec((None, block_q, 1),
                         lambda b_, h_, i, j, *_: (b_, i, 0)),
            pl.BlockSpec((None, 1, block_k),
                         lambda b_, h_, i, j, *_: (b_, 0, j)),
        ],
        out_specs=pl.BlockSpec((None, None, block_q, d),
                               lambda b_, h_, i, j, *_: (b_, h_, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, t, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q_first, k_last, _head_major(q), _head_major(k), _head_major(v),
      seg[:, :, None], seg[:, None, :])
    return _head_major(out)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 512, block_k: int = 512,
                    interpret: bool = False):
    """q: (B,S,H,D); k,v: (B,S,KV,D) -> (B,S,H,D)."""
    b, s, h, d = q.shape
    kvh = k.shape[2]
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    assert s % block_q == 0 and s % block_k == 0, (s, block_q, block_k)
    nq, nk = s // block_q, s // block_k
    scale = 1.0 / np.sqrt(d)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, nk=nk)

    def kv_map(b_, h_, i, j):
        return (b_, h_ * kvh // h, j, 0)

    out = pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((None, None, block_q, d),
                         lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((None, None, block_k, d), kv_map),
            pl.BlockSpec((None, None, block_k, d), kv_map),
        ],
        out_specs=pl.BlockSpec((None, None, block_q, d),
                               lambda b_, h_, i, j: (b_, h_, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(_head_major(q), _head_major(k), _head_major(v))
    return _head_major(out)
