"""Single-token (decode) GQA attention over a KV cache as a Pallas kernel.

The serving hot loop: one query token per sequence against a long cache.
TPU design: grid (batch, cache_blocks) with the cache-block dim innermost;
each grid cell streams one ``(block_k, KV, D)`` block of the row's cache
HBM→VMEM — all kv heads at once, because the cache's last two dims are
(KV, D) and Mosaic lowers a block only when those are whole (or 8/128
aligned) — and loops over the kv heads inside the kernel with a static
loop. The (KV, rep, D) query groups stay resident in VMEM; online softmax
per head in VMEM scratch. GQA is native — head ``g`` attends with its
``rep = H/KV`` query heads, so the cache is never head-repeated (the jnp
lesson from EXPERIMENTS §Perf #9, here enforced structurally).

Raggedness: ``lengths`` is a per-sequence ``(B,)`` int32 vector (a scalar
is accepted and broadcast). It is delivered via scalar prefetch
(``pltpu.PrefetchScalarGridSpec``) so it is available to the *index maps*,
not just the kernel body:

  * compute skip — cache blocks entirely past a row's length are skipped
    with ``pl.when`` (the same fully-masked-tile skip proven in
    ``flash_attention``), so a 100-token row in a 4096-slot cache does 1
    block of work, not 32;
  * DMA skip — the K/V index map clamps the block index to the row's last
    valid block, so Pallas's revisit-elision never streams dead cache
    blocks from HBM. Bandwidth, not just FLOPs, scales with actual
    sequence length — that is the entire game for decode attention, which
    is memory-bound.

Rows with ``lengths == 0`` produce exact zeros (no blocks run; the
finalizer's ``l`` guard returns 0), which slot-based continuous batching
relies on for vacant slots.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref,
                   m_scr, l_scr, acc_scr, *, scale: float, block_k: int,
                   nk: int, kvh: int):
    bi = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    valid = len_ref[bi]
    k_start = j * block_k

    @pl.when(k_start < valid)
    def _compute():
        for g in range(kvh):
            q = q_ref[g].astype(jnp.float32)           # (rep, D)
            k = k_ref[:, g, :].astype(jnp.float32)     # (block_k, D)
            v = v_ref[:, g, :].astype(jnp.float32)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = s * scale
            kpos = k_start + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            s = jnp.where(kpos < valid, s, NEG_INF)
            m_prev = m_scr[g]
            m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_scr[g] = l_scr[g] * corr + p.sum(-1, keepdims=True)
            acc_scr[g] = acc_scr[g] * corr + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_scr[g] = m_new

    @pl.when(j == nk - 1)
    def _finalize():
        o_ref[...] = (acc_scr[...]
                      / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def decode_attention(q, k_cache, v_cache, lengths, *, block_k: int = 512,
                     interpret: bool = False):
    """q: (B, H, D); caches: (B, C, KV, D); lengths: int32 scalar or (B,).

    Returns (B, H, D). Rows with length 0 return zeros."""
    b, h, d = q.shape
    _, c, kvh, _ = k_cache.shape
    rep = h // kvh
    block_k = min(block_k, c)
    while block_k > 1 and c % block_k:      # largest divisor <= requested
        block_k //= 2
    assert c % block_k == 0, (c, block_k)
    nk = c // block_k
    qg = q.reshape(b, kvh, rep, d)
    lengths = jnp.broadcast_to(
        jnp.asarray(lengths, jnp.int32).reshape(-1), (b,))
    kernel = functools.partial(_decode_kernel, scale=1.0 / np.sqrt(d),
                               block_k=block_k, nk=nk, kvh=kvh)

    def kv_map(b_, j, len_ref):
        # Clamp past-length blocks onto the row's last live block: Pallas
        # elides the DMA when the block index repeats, so dead cache never
        # leaves HBM.
        last = jnp.maximum((len_ref[b_] + block_k - 1) // block_k, 1) - 1
        return (b_, jnp.minimum(j, last), 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, nk),
        in_specs=[
            pl.BlockSpec((None, kvh, rep, d),
                         lambda b_, j, len_ref: (b_, 0, 0, 0)),
            pl.BlockSpec((None, block_k, kvh, d), kv_map),
            pl.BlockSpec((None, block_k, kvh, d), kv_map),
        ],
        out_specs=pl.BlockSpec((None, kvh, rep, d),
                               lambda b_, j, len_ref: (b_, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((kvh, rep, 1), jnp.float32),
            pltpu.VMEM((kvh, rep, 1), jnp.float32),
            pltpu.VMEM((kvh, rep, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, rep, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(lengths, qg, k_cache, v_cache)
    return out.reshape(b, h, d)
