"""Mamba2 SSD (state-space duality) chunked scan as a Pallas TPU kernel.

TPU adaptation of the SSD block decomposition (arXiv:2405.21060 §6): the
sequence is split into chunks; within a chunk the dual *quadratic* form runs
on the MXU (two (cl,cl)·(cl,P) matmuls — exactly what the systolic array
wants), while the O(1)-state inter-chunk recurrence is carried in VMEM
scratch across sequential grid steps. This replaces the GPU formulation's
warp-level associative scan — on TPU the scan is simply the innermost grid
dimension with "arbitrary" semantics.

Inputs are pre-arranged by ``ops.ssd`` to chunk blocks:
  xdt:   (BH, NC, cl, P)   — dt-scaled inputs, one row per (batch, head)
  a_col: (BH, NC, cl, 1)   — inclusive within-chunk cumsum of dt·A (the
  a_row: (BH, NC, 1, cl)     negative log-decays), as a column and a row
  b,c:   (B, NC, cl, N)    — input/output projections, shared across heads
                             (the index map reads row ``g // H``)
  s0:    (BH, N, P)        — the state the scan starts from
Outputs: y (BH, NC, cl, P) and the final state (BH, N, P).

The cumulative sum is computed by the wrapper: Mosaic has no lowering for
``jnp.cumsum``, and both orientations of it are needed for the (cl, cl)
decay matrix. Every block's last two dims are whole (cl, P, N, 1), which
is what the Mosaic lowering asks of a block that is not (8, 128) aligned.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(xdt_ref, acol_ref, arow_ref, b_ref, c_ref, s0_ref, y_ref,
                state_ref, s_scr, *, nc: int, cl: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        s_scr[...] = s0_ref[...].astype(jnp.float32)

    xdt = xdt_ref[...].astype(jnp.float32)           # (cl, P)
    a_col = acol_ref[...]                            # (cl, 1) inclusive cumsum
    a_row = arow_ref[...]                            # (1, cl)
    a_total = a_row[:, cl - 1:]                      # (1, 1)
    b = b_ref[...].astype(jnp.float32)               # (cl, N)
    c = c_ref[...].astype(jnp.float32)               # (cl, N)

    # intra-chunk: Y_diag = (C·Bᵀ ⊙ L) @ xdt, L[i,j] = exp(a_cs[i]-a_cs[j])·[i>=j]
    cb = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)     # (cl, cl)
    ii = jax.lax.broadcasted_iota(jnp.int32, (cl, cl), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (cl, cl), 1)
    lmask = jnp.where(ii >= jj, jnp.exp(a_col - a_row), 0.0)
    y_diag = jax.lax.dot_general(cb * lmask, xdt, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # (cl, P)

    # contribution of the inbound state
    s_prev = s_scr[...]                              # (N, P)
    c_in = c * jnp.exp(a_col)                        # decay from chunk start
    y_off = jax.lax.dot_general(c_in, s_prev, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)

    y_ref[...] = (y_diag + y_off).astype(y_ref.dtype)

    # state update: S ← S·exp(Σa) + Σ_t exp(a_cs[-1]-a_cs[t])·b_t ⊗ xdt_t
    b_w = b * jnp.exp(a_total - a_col)               # (cl, N)
    s_new = s_prev * jnp.exp(a_total) + jax.lax.dot_general(
        b_w.T, xdt, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    s_scr[...] = s_new

    @pl.when(ci == nc - 1)
    def _final():
        state_ref[...] = s_new.astype(state_ref.dtype)


def ssd_scan(xdt, a_cs, b, c, s0, *, interpret: bool = False):
    """xdt: (BH, NC, cl, P); a_cs: (BH, NC, cl) within-chunk inclusive
    cumsum of dt·A; b,c: (B, NC, cl, N); s0: (BH, N, P)."""
    bh, nc, cl, p = xdt.shape
    n = b.shape[-1]
    heads = bh // b.shape[0]
    kernel = functools.partial(_ssd_kernel, nc=nc, cl=cl)
    a_cs = a_cs.astype(jnp.float32)
    return pl.pallas_call(
        kernel,
        grid=(bh, nc),
        in_specs=[
            pl.BlockSpec((None, None, cl, p), lambda g, ci: (g, ci, 0, 0)),
            pl.BlockSpec((None, None, cl, 1), lambda g, ci: (g, ci, 0, 0)),
            pl.BlockSpec((None, None, 1, cl), lambda g, ci: (g, ci, 0, 0)),
            pl.BlockSpec((None, None, cl, n),
                         lambda g, ci: (g // heads, ci, 0, 0)),
            pl.BlockSpec((None, None, cl, n),
                         lambda g, ci: (g // heads, ci, 0, 0)),
            pl.BlockSpec((None, n, p), lambda g, ci: (g, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, None, cl, p), lambda g, ci: (g, ci, 0, 0)),
            pl.BlockSpec((None, n, p), lambda g, ci: (g, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, nc, cl, p), xdt.dtype),
            jax.ShapeDtypeStruct((bh, n, p), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(xdt, a_cs[..., None], a_cs[:, :, None, :], b, c, s0)
