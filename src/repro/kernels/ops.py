"""Jit'd public wrappers around the Pallas kernels, with backend dispatch.

Backends:
  * ``pallas``    — compiled Pallas TPU kernel (TARGET hardware),
  * ``interpret`` — same kernel body executed in Python on CPU (validation),
  * ``jnp``       — pure-jnp chunked implementation (used on the CPU build
                    machine and inside the multi-device dry-run, where XLA
                    cost analysis of standard HLO is what the roofline reads).

``default_backend()`` picks ``pallas`` on real TPUs and ``jnp`` elsewhere.
It is the one place that choice is made: the model layers
(``repro.models.layers``) ask it before every kernel dispatch, so a test
that replaces it with ``lambda: "interpret"`` runs the whole serving path
through the kernel bodies on the CPU.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import ssd_scan as _ssd
from repro.kernels import ref as _ref


def default_backend() -> str:
    return "pallas" if jax.default_backend() == "tpu" else "jnp"


# --------------------------------------------------------------------------
# attention
# --------------------------------------------------------------------------
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 512, block_k: int = 512,
                    backend: Optional[str] = None):
    backend = backend or default_backend()
    if backend in ("pallas", "interpret"):
        return _fa.flash_attention(
            q, k, v, causal=causal, window=window, block_q=block_q,
            block_k=block_k, interpret=(backend == "interpret"))
    if backend == "jnp":
        from repro.models.layers import attention_chunked
        return attention_chunked(q, k, v, causal=causal, window=window,
                                 chunk_q=block_q, chunk_k=block_k)
    if backend == "ref":
        return _ref.attention_ref(q, k, v, causal=causal, window=window)
    raise ValueError(backend)


def segment_flash_attention(q, k, v, seg_ids, *, window: int = 0,
                            block_q: int = 512, block_k: int = 512,
                            backend: Optional[str] = None):
    """Segment-masked causal attention over a packed ragged-prefill row."""
    backend = backend or default_backend()
    if backend in ("pallas", "interpret"):
        return _fa.segment_flash_attention(
            q, k, v, seg_ids, window=window, block_q=block_q,
            block_k=block_k, interpret=(backend == "interpret"))
    if backend == "ref":
        return _ref.packed_attention_ref(q, k, v, seg_ids, window=window)
    raise ValueError(backend)


# --------------------------------------------------------------------------
# SSD (mamba2)
# --------------------------------------------------------------------------
def _ssd_chunked_jnp(x, dt, a, b, c, chunk: int, initial_state=None):
    """Vectorized chunked SSD in plain jnp (same math as the Pallas kernel).

    x: (B,L,H,P) dt: (B,L,H) a: (H,) b,c: (B,L,N) -> (y, final_state(B,H,N,P))
    """
    bs, l0, h, p = x.shape
    n = b.shape[-1]
    cl = min(chunk, l0)
    pad = (-l0) % cl
    if pad:
        # dt=0 padding is exact: decay=exp(0)=1, update=0 → state unchanged
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        b = jnp.pad(b, ((0, 0), (0, pad), (0, 0)))
        c = jnp.pad(c, ((0, 0), (0, pad), (0, 0)))
    l = l0 + pad
    nc = l // cl

    f32 = jnp.float32
    adt = dt.astype(f32) * a.astype(f32)                   # (B,L,H)
    xdt = x.astype(f32) * dt.astype(f32)[..., None]        # (B,L,H,P)

    adt = adt.reshape(bs, nc, cl, h)
    xdt = xdt.reshape(bs, nc, cl, h, p)
    bc = b.astype(f32).reshape(bs, nc, cl, n)
    cc = c.astype(f32).reshape(bs, nc, cl, n)

    a_cs = jnp.cumsum(adt, axis=2)                         # (B,NC,cl,H)
    a_tot = a_cs[:, :, -1, :]                              # (B,NC,H)

    cb = jnp.einsum("bcin,bcjn->bcij", cc, bc)
    tri = jnp.tril(jnp.ones((cl, cl), bool))
    lmask = jnp.where(tri[None, None, :, :, None],
                      jnp.exp(a_cs[:, :, :, None, :] - a_cs[:, :, None, :, :]),
                      0.0)                                  # (B,NC,cl,cl,H)
    y_diag = jnp.einsum("bcij,bcijh,bcjhp->bcihp", cb, lmask, xdt)

    decay_out = jnp.exp(a_tot[:, :, None, :] - a_cs)       # (B,NC,cl,H)
    states = jnp.einsum("bcjn,bcjh,bcjhp->bchnp", bc, decay_out, xdt)

    s0 = (jnp.zeros((bs, h, n, p), f32) if initial_state is None
          else initial_state.astype(f32))

    def step(s, xs):
        st, dec = xs                                       # (B,H,N,P), (B,H)
        s_next = s * jnp.exp(dec)[..., None, None] + st
        return s_next, s                                   # emit state BEFORE chunk

    final, s_prev = jax.lax.scan(
        step, s0, (states.swapaxes(0, 1), a_tot.swapaxes(0, 1)))
    s_prev = s_prev.swapaxes(0, 1)                         # (B,NC,H,N,P)

    y_off = jnp.einsum("bcin,bchnp,bcih->bcihp", cc, s_prev, jnp.exp(a_cs))
    y = (y_diag + y_off).reshape(bs, l, h, p)[:, :l0]
    return y.astype(x.dtype), final


def ssd(x, dt, a, b, c, *, chunk: int = 128, backend: Optional[str] = None,
        initial_state=None) -> Tuple[jax.Array, jax.Array]:
    """Chunked SSD. Returns (y (B,L,H,P), final_state (B,H,N,P))."""
    backend = backend or default_backend()
    if backend == "ref":
        return _ref.ssd_ref(x, dt, a, b, c, initial_state=initial_state)
    if backend == "jnp":
        return _ssd_chunked_jnp(x, dt, a, b, c, chunk, initial_state)
    # pallas / interpret — pad like the jnp path (dt=0 padding is exact),
    # then pre-arrange to (B·H, NC, cl, ·) chunk blocks. The chunk is
    # rounded up to a sublane multiple so short prompts keep aligned tiles.
    bs, l0, h, p = x.shape
    n = b.shape[-1]
    cl = min(chunk, -(-l0 // 8) * 8)
    pad = (-l0) % cl
    f32 = jnp.float32
    xdt = x.astype(f32) * dt.astype(f32)[..., None]       # (B,L,H,P)
    adt = dt.astype(f32) * a.astype(f32)                  # (B,L,H)
    if pad:
        xdt = jnp.pad(xdt, ((0, 0), (0, pad), (0, 0), (0, 0)))
        adt = jnp.pad(adt, ((0, 0), (0, pad), (0, 0)))
        b = jnp.pad(b, ((0, 0), (0, pad), (0, 0)))
        c = jnp.pad(c, ((0, 0), (0, pad), (0, 0)))
    l = l0 + pad
    nc = l // cl
    xdt = xdt.transpose(0, 2, 1, 3).reshape(bs * h, nc, cl, p)
    a_cs = jnp.cumsum(adt.transpose(0, 2, 1).reshape(bs * h, nc, cl), -1)
    s0 = (jnp.zeros((bs * h, n, p), f32) if initial_state is None
          else initial_state.astype(f32).reshape(bs * h, n, p))
    y, state = _ssd.ssd_scan(
        xdt, a_cs, b.astype(f32).reshape(bs, nc, cl, n),
        c.astype(f32).reshape(bs, nc, cl, n), s0,
        interpret=(backend == "interpret"))
    y = y.reshape(bs, h, l, p).transpose(0, 2, 1, 3)[:, :l0].astype(x.dtype)
    state = state.reshape(bs, h, n, p)
    return y, state


def ssd_decode(x, dt, a, b, c, state):
    """One-token SSD update (no kernel needed — pure elementwise + matvec)."""
    return _ref.ssd_decode_ref(x, dt, a, b, c, state)
