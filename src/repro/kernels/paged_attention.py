"""Block-table-indexed (paged) single-token decode attention as a Pallas kernel.

The paged sibling of ``repro.kernels.decode_attention``: instead of one
contiguous ``(B, C, KV, D)`` ring per sequence, K/V live in a shared pool of
fixed-size pages and each sequence owns an ordered list of page ids — its
*block table*, a ``(B, max_pages)`` int32 row (the vLLM PagedAttention
layout). A sequence's logical cache position ``t`` lives at page
``block_tables[b, t // page_size]``, row ``t % page_size``.

The pool is HEAD-MAJOR and STACKED over layers, ``(L, KV, P, page_size,
D)`` — the serving cache's own leaf, passed whole with a layer index. One
page of one kv head of one layer is then a ``(page_size, D)`` tile whose
last two dims are what the Mosaic lowering tiles (sublane x lane): a
page-major pool would put the kv-head axis second-to-last, which Mosaic
refuses to block at 1. The layer is picked by the K/V index maps (the
layer index rides in by scalar prefetch), never by slicing the pool
outside the kernel: a slice of one layer is a 134 MB copy at olmo-1b's
serving size, and the compiler made four of them per layer and pool
around the call (slice, relayout for the token write, relayout back,
update-slice into the stack). A single-layer ``(KV, P, page_size, D)``
pool is the special case ``layer=None``.

TPU design mirrors the ragged decode kernel: grid ``(batch, kv_heads,
max_pages)`` with the page dim innermost; the ``(rep, D)`` query group stays
resident in VMEM while pages stream HBM→VMEM; online softmax in VMEM
scratch. Both the block table (flattened to 1-D, which SMEM stores without
padding) AND the per-sequence lengths ride in via scalar prefetch
(``pltpu.PrefetchScalarGridSpec``), with the layer index, so they are
available to the *index maps*, which is where paging actually happens:

  * indirection — the K/V index map looks the j-th logical page up in the
    block table, so the kernel walks each row's pages in logical order no
    matter where they sit in the physical pool;
  * compute skip — pages entirely past a row's length are skipped with
    ``pl.when`` (same fully-masked-tile skip as ``decode_attention``);
  * DMA skip — past-length lookups clamp onto the row's last live page, so
    Pallas's revisit-elision never streams dead pages from HBM. Bandwidth
    scales with each row's actual length, not with ``max_pages``.

Rows with ``lengths == 0`` produce exact zeros (no pages run; the
finalizer's ``l`` guard returns 0) — vacant continuous-batching slots point
their whole table row at the reserved null page and cost nothing.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_kernel(tbl_ref, len_ref, layer_ref, q_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr, *, scale: float, page_size: int,
                  n_pages: int):
    bi = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    valid = len_ref[bi]
    k_start = j * page_size

    @pl.when(k_start < valid)
    def _compute():
        q = q_ref[...].astype(jnp.float32)             # (rep, D)
        k = k_ref[...].astype(jnp.float32)             # (page_size, D)
        v = v_ref[...].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kpos < valid, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(j == n_pages - 1)
    def _finalize():
        o_ref[...] = (acc_scr[...]
                      / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def paged_decode_attention(q, k_pages, v_pages, block_tables, lengths,
                           layer=None, *, interpret: bool = False):
    """q: (B, H, D); pages: (L, KV, P, page_size, D) stacked head-major
    pools read at ``layer`` (an int32 scalar, traced or not), or one
    layer's (KV, P, page_size, D) pools with ``layer`` None;
    block_tables: (B, max_pages) int32 page ids; lengths: int32 scalar or
    (B,).

    Returns (B, H, D). Rows with length 0 return zeros. Table entries at or
    past a row's last live page are never dereferenced (the index map clamps
    onto the last live page), so padding rows with any page id — by
    convention the null page 0 — is safe."""
    if layer is None:
        k_pages, v_pages, layer = k_pages[None], v_pages[None], 0
    b, h, d = q.shape
    _, kvh, _, page_size, _ = k_pages.shape
    max_pages = block_tables.shape[1]
    rep = h // kvh
    qg = q.reshape(b, kvh, rep, d)
    lengths = jnp.broadcast_to(
        jnp.asarray(lengths, jnp.int32).reshape(-1), (b,))
    block_tables = jnp.asarray(block_tables, jnp.int32).reshape(-1)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    kernel = functools.partial(_paged_kernel, scale=1.0 / np.sqrt(d),
                               page_size=page_size, n_pages=max_pages)

    def kv_map(b_, g, j, tbl_ref, len_ref, layer_ref):
        # Clamp past-length logical pages onto the row's last live one —
        # the block index then repeats and Pallas elides the DMA, so dead
        # pages never leave HBM. The table lookup is the paging itself.
        last = jnp.maximum(
            (len_ref[b_] + page_size - 1) // page_size, 1) - 1
        page = tbl_ref[b_ * max_pages + jnp.minimum(j, last)]
        return (layer_ref[0], g, page, 0, 0)

    def row_map(b_, g, j, tbl_ref, len_ref, layer_ref):
        return (b_, g, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, kvh, max_pages),
        in_specs=[
            pl.BlockSpec((None, None, rep, d), row_map),
            pl.BlockSpec((None, None, None, page_size, d), kv_map),
            pl.BlockSpec((None, None, None, page_size, d), kv_map),
        ],
        out_specs=pl.BlockSpec((None, None, rep, d), row_map),
        scratch_shapes=[
            pltpu.VMEM((rep, 1), jnp.float32),
            pltpu.VMEM((rep, 1), jnp.float32),
            pltpu.VMEM((rep, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, rep, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(block_tables, lengths, layer, qg, k_pages, v_pages)
    return out.reshape(b, h, d)
