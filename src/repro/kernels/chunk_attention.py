"""Incremental chunk attention over paged K/V as a Pallas kernel.

The prefill-continuation sibling of ``repro.kernels.paged_attention``: a
segment of R *new* tokens attends (a) the K/V its sequence already wrote
into the shared page pool — looked up through the segment's block-table
row, exactly like paged decode — and (b) the chunk's own K/V causally.
One kernel powers two serving paths: chunked-prefill continuations (only
the new chunk is computed, dropping continuation cost from O(L²/chunk)
to O(chunk)) and speculative-decoding verification (the k draft tokens
are the chunk; their logits score the draft in one dispatch).

TPU design mirrors the paged decode kernel: the pool is head-major and
stacked over layers, ``(L, KV, P, page_size, D)``, read at a layer index
that the history index map applies (no slice of the pool leaves the
kernel: a scan over the pool as ``xs`` made two whole-layer copies per
layer of each continuation chunk), and the wrapper lays the chunk's
queries and K/V out head-major too, so every block's last two dims are a
(rows, D) tile Mosaic can lower. Grid ``(segments, kv_heads, max_pages +
1)`` with the page dim innermost. Iterations ``j < max_pages`` stream
history pages HBM→VMEM with the same block-table index map —
past-history lookups clamp onto the last live page so revisit-elision
never DMAs dead pages; the final iteration ``j == max_pages`` attends the
chunk's own rows under a local causal mask and finalizes the online
softmax. Block tables, history lengths, segment lengths and the layer
index all ride in via scalar prefetch so the index maps can page.

Rows r >= the segment's length are unspecified (padding); callers slice
the valid region. History length 0 (a fresh sequence) is fine — the
chunk's causal part always has at least the query itself.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _chunk_kernel(tbl_ref, hist_ref, slen_ref, layer_ref, q_ref, kh_ref,
                  vh_ref, kc_ref, vc_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, page_size: int, n_pages: int, rep: int,
                  chunk: int, window: int):
    si = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    hist = hist_ref[si]
    slen = slen_ref[si]
    # query rows flatten to (chunk * rep, D); row f belongs to chunk
    # position f // rep at absolute position hist + f // rep
    qrow = jax.lax.broadcasted_iota(jnp.int32, (chunk * rep, 1), 0) // rep
    qpos = hist + qrow

    def _online(s, v):
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(jnp.logical_and(j < n_pages, j * page_size < hist))
    def _history():
        q = q_ref[...].astype(jnp.float32)              # (chunk*rep, D)
        k = kh_ref[...].astype(jnp.float32)             # (page_size, D)
        v = vh_ref[...].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        kpos = (j * page_size
                + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1))
        mask = kpos < hist
        if window:
            mask = jnp.logical_and(mask, qpos - kpos < window)
        s = jnp.where(mask, s, NEG_INF)
        _online(s, v)

    @pl.when(j == n_pages)
    def _local():
        q = q_ref[...].astype(jnp.float32)              # (chunk*rep, D)
        k = kc_ref[...].astype(jnp.float32)             # (chunk, D)
        v = vc_ref[...].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        kcol = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = jnp.logical_and(kcol <= qrow, kcol < slen)
        if window:
            mask = jnp.logical_and(mask, qrow - kcol < window)
        s = jnp.where(mask, s, NEG_INF)
        _online(s, v)
        o_ref[...] = (acc_scr[...]
                      / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def paged_chunk_attention(q, k_pages, v_pages, k_chunk, v_chunk,
                          block_tables, hist_lens, seg_lens, layer=None, *,
                          window: int = 0, interpret: bool = False):
    """q/k_chunk/v_chunk: (S, R, H|KV, D) — R chunk rows per segment;
    pages: (L, KV, P, page_size, D) stacked head-major pools read at
    ``layer`` (an int32 scalar), or one layer's (KV, P, page_size, D)
    pools with ``layer`` None; block_tables: (S, max_pages) int32;
    hist_lens/seg_lens: (S,) int32.

    Chunk row r of segment s sits at absolute position hist_lens[s] + r;
    it attends paged history [0, hist_lens[s]) plus chunk rows [0, r]
    with r < seg_lens[s]. Returns (S, R, H, D); rows r >= seg_lens[s]
    are unspecified padding. Table entries at or past the last live
    history page are never dereferenced (the index map clamps)."""
    if layer is None:
        k_pages, v_pages, layer = k_pages[None], v_pages[None], 0
    s_, r, h, d = q.shape
    _, kvh, _, page_size, _ = k_pages.shape
    max_pages = block_tables.shape[1]
    rep = h // kvh
    # flatten queries to (R*rep, D) rows per kv head: row p*rep + u is
    # chunk position p's u-th grouped query (matches the kernel's // rep)
    qg = q.reshape(s_, r, kvh, rep, d).transpose(0, 2, 1, 3, 4)
    qg = qg.reshape(s_, kvh, r * rep, d)
    kc = k_chunk.transpose(0, 2, 1, 3)                  # (S, KV, R, D)
    vc = v_chunk.transpose(0, 2, 1, 3)
    hist_lens = jnp.asarray(hist_lens, jnp.int32).reshape(-1)
    seg_lens = jnp.asarray(seg_lens, jnp.int32).reshape(-1)
    block_tables = jnp.asarray(block_tables, jnp.int32).reshape(-1)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    kernel = functools.partial(
        _chunk_kernel, scale=1.0 / np.sqrt(d), page_size=page_size,
        n_pages=max_pages, rep=rep, chunk=r, window=window)

    def hist_map(s_i, g, j, tbl_ref, hist_ref, slen_ref, layer_ref):
        # clamp past-history logical pages onto the last live one so the
        # repeated block index elides the DMA (dead pages stay in HBM);
        # the j == max_pages iteration reuses the last page harmlessly
        last = jnp.maximum(
            (hist_ref[s_i] + page_size - 1) // page_size, 1) - 1
        page = tbl_ref[s_i * max_pages + jnp.minimum(j, last)]
        return (layer_ref[0], g, page, 0, 0)

    def chunk_map(s_i, g, j, tbl_ref, hist_ref, slen_ref, layer_ref):
        return (s_i, g, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(s_, kvh, max_pages + 1),
        in_specs=[
            pl.BlockSpec((None, None, r * rep, d), chunk_map),
            pl.BlockSpec((None, None, None, page_size, d), hist_map),
            pl.BlockSpec((None, None, None, page_size, d), hist_map),
            pl.BlockSpec((None, None, r, d), chunk_map),
            pl.BlockSpec((None, None, r, d), chunk_map),
        ],
        out_specs=pl.BlockSpec((None, None, r * rep, d), chunk_map),
        scratch_shapes=[
            pltpu.VMEM((r * rep, 1), jnp.float32),
            pltpu.VMEM((r * rep, 1), jnp.float32),
            pltpu.VMEM((r * rep, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s_, kvh, r * rep, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(block_tables, hist_lens, seg_lens, layer, qg, k_pages, v_pages, kc,
      vc)
    out = out.reshape(s_, kvh, r, rep, d)
    return out.transpose(0, 2, 1, 3, 4).reshape(s_, r, h, d)
