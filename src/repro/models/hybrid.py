"""Zamba2-style hybrid: Mamba2 backbone + one *shared* attention block.

81 mamba2 layers; a single weight-tied full-attention block (attn + SwiGLU
MLP) is applied after every ``cfg.attn_every``-th layer. Because the
attention weights are shared, the scan over mamba layers can invoke it via
``jax.lax.cond`` inside the scan body — per-invocation KV caches are indexed
by ``layer_idx // attn_every``.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models import ssm
from repro.models.layers import ParamDef


def n_attn_blocks(cfg) -> int:
    return cfg.num_layers // cfg.attn_every


def shared_attn_plan(cfg) -> dict:
    return {
        "ln1": L.norm_plan(cfg.d_model, cfg.norm),
        "attn": L.attn_plan(cfg),
        "ln2": L.norm_plan(cfg.d_model, cfg.norm),
        "mlp": L.mlp_plan(cfg),
    }


def plan(cfg) -> dict:
    return {
        "embed": L.embed_plan(cfg),
        "layers": L.stack_plan(ssm.mamba_layer_plan(cfg), cfg.num_layers),
        "shared_attn": shared_attn_plan(cfg),
        "final_norm": L.norm_plan(cfg.d_model, cfg.norm),
    }


def init(key, cfg, dtype=jnp.float32):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "embed": L.init_from_plan(k1, L.embed_plan(cfg), dtype),
        "layers": L.init_stacked(k2, ssm.mamba_layer_plan(cfg), cfg.num_layers, dtype),
        "shared_attn": L.init_from_plan(k3, shared_attn_plan(cfg), dtype),
        "final_norm": L.init_from_plan(k4, L.norm_plan(cfg.d_model, cfg.norm), dtype),
    }


def _apply_shared_full(sp, cfg, x, positions):
    h = L.apply_norm(sp["ln1"], x, cfg.norm)
    q, k, v = L.attn_qkv(sp["attn"], cfg, h, positions)
    q = L.constrain_q_prefill(cfg, q)
    attn = L.big_attention(q, k, v, causal=True)
    x = x + L.attn_out(sp["attn"], x.dtype, attn)
    h = L.apply_norm(sp["ln2"], x, cfg.norm)
    return x + L.apply_mlp(sp["mlp"], h), (k, v)


def forward(params, cfg, tokens, *, remat: bool = False):
    dtype = jnp.dtype(cfg.dtype)
    x = L.embed_tokens(params["embed"], tokens, dtype)
    positions = jnp.arange(tokens.shape[1])[None, :]
    sp = params["shared_attn"]

    from repro.utils.sharding import maybe_constrain

    def body(carry, xs):
        lp, idx = xs
        y, _ = ssm.mamba_block(lp, cfg, carry)
        y = jax.lax.cond(
            (idx + 1) % cfg.attn_every == 0,
            lambda t: _apply_shared_full(sp, cfg, t, positions)[0],
            lambda t: t,
            y)
        y = maybe_constrain(y, "batch", None, "act_embed")
        return y, None

    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(
        body, x, (params["layers"], jnp.arange(cfg.num_layers)))
    x = L.apply_norm(params["final_norm"], x, cfg.norm)
    logits = L.unembed(params["embed"], x, cfg)
    aux = {"load_balance_loss": jnp.float32(0.0),
           "dropped_fraction": jnp.float32(0.0)}
    return logits, aux


# --------------------------------------------------------------------------
# serving
# --------------------------------------------------------------------------
# only the shared-attention KV is paged; the mamba state is O(1) per row
# and stays per-slot dense (paging a fixed-size state buys nothing)
PAGED_KEYS = ("attn_k", "attn_v")


def cache_plan(cfg, batch: int, cache_len: int) -> dict:
    base = ssm.cache_plan(cfg, batch, cache_len)
    na = n_attn_blocks(cfg)
    kv_shape = (na, batch, cache_len, cfg.num_kv_heads, cfg.resolved_head_dim)
    spec = L.kv_cache_spec(cfg)
    base["attn_k"] = ParamDef(kv_shape, spec, "zeros")
    base["attn_v"] = ParamDef(kv_shape, spec, "zeros")
    return base


def init_cache(cfg, batch: int, cache_len: int, dtype=None):
    dtype = dtype or jnp.dtype(cfg.dtype)
    cache = ssm.init_cache(cfg, batch, cache_len, dtype)
    cp = cache_plan(cfg, batch, cache_len)
    cache["attn_k"] = jnp.zeros(cp["attn_k"].shape, dtype)
    cache["attn_v"] = jnp.zeros(cp["attn_v"].shape, dtype)
    return cache


def paged_cache_plan(cfg, batch: int, num_pages: int, page_size: int,
                     max_pages: int) -> dict:
    base = ssm.cache_plan(cfg, batch, 0)
    kv = L.paged_kv_plan(cfg, n_attn_blocks(cfg), num_pages, page_size)
    base["attn_k"] = kv
    base["attn_v"] = kv
    base["block_tables"] = ParamDef((batch, max_pages), None, "zeros")
    return base


def init_paged_cache(cfg, batch: int, num_pages: int, page_size: int,
                     max_pages: int, dtype=None):
    dtype = dtype or jnp.dtype(cfg.dtype)
    cache = ssm.init_cache(cfg, batch, 0, dtype)
    cp = paged_cache_plan(cfg, batch, num_pages, page_size, max_pages)
    cache["attn_k"] = jnp.zeros(cp["attn_k"].shape, dtype)
    cache["attn_v"] = jnp.zeros(cp["attn_v"].shape, dtype)
    cache["block_tables"] = jnp.zeros((batch, max_pages), jnp.int32)
    return cache


def prefill(params, cfg, tokens, cache_len: int):
    dtype = jnp.dtype(cfg.dtype)
    b, s = tokens.shape
    x = L.embed_tokens(params["embed"], tokens, dtype)
    positions = jnp.arange(s)[None, :]
    sp = params["shared_attn"]
    na = n_attn_blocks(cfg)

    def body(carry, xs):
        h, kc, vc = carry
        lp, idx = xs
        h, (state, conv_tail) = ssm.mamba_block(lp, cfg, h)

        def attn_branch(args):
            h_, kc_, vc_ = args
            h2, (k, v) = _apply_shared_full(sp, cfg, h_, positions)
            if s <= cache_len:
                kk = jnp.zeros((b, cache_len) + k.shape[2:], k.dtype).at[:, :s].set(k)
                vv = jnp.zeros((b, cache_len) + v.shape[2:], v.dtype).at[:, :s].set(v)
            else:
                kk, vv = k[:, s - cache_len:], v[:, s - cache_len:]
            j = jnp.minimum(idx // cfg.attn_every, na - 1)
            kc_ = jax.lax.dynamic_update_slice_in_dim(kc_, kk[None], j, axis=0)
            vc_ = jax.lax.dynamic_update_slice_in_dim(vc_, vv[None], j, axis=0)
            return h2, kc_, vc_

        h, kc, vc = jax.lax.cond(
            (idx + 1) % cfg.attn_every == 0, attn_branch,
            lambda args: args, (h, kc, vc))
        return (h, kc, vc), (state, conv_tail)

    na_shape = (na, b, cache_len, cfg.num_kv_heads, cfg.resolved_head_dim)
    kc0 = jnp.zeros(na_shape, dtype)
    vc0 = jnp.zeros(na_shape, dtype)
    (x, kc, vc), (states, convs) = jax.lax.scan(
        body, (x, kc0, vc0),
        (params["layers"], jnp.arange(cfg.num_layers)))
    x = L.apply_norm(params["final_norm"], x[:, -1], cfg.norm)
    logits = L.unembed(params["embed"], x, cfg)
    return logits, {"ssm": states, "conv": convs, "attn_k": kc, "attn_v": vc,
                    "pos": jnp.full((b,), s, jnp.int32)}


def prefill_packed(params, cfg, packed, max_seg_len: int):
    """Packed ragged prefill: mamba backbone with per-segment state resets
    (``ssm.mamba_block_packed``) + the shared attention block run
    segment-masked over the packed row. The shared-attention K/V stays in
    PACKED per-token order (na, T, KV, D) so the engine can scatter each
    segment's tokens straight into its slot's pages; mamba state/conv are
    per-segment rows like the pure-SSM family."""
    tokens = packed["tokens"]
    seg_ids, seg_starts = packed["seg_ids"], packed["seg_starts"]
    seg_lens = packed["seg_lens"]
    dtype = jnp.dtype(cfg.dtype)
    b, t = tokens.shape
    x = L.embed_tokens(params["embed"], tokens, dtype)
    pos = L.packed_positions(seg_ids, seg_starts)
    positions = pos[None, :]
    sp = params["shared_attn"]
    na = n_attn_blocks(cfg)

    def body(carry, xs):
        h, kc, vc = carry
        lp, idx = xs
        h, (states, tails) = ssm.mamba_block_packed(
            lp, cfg, h, seg_ids, pos, seg_starts, seg_lens, max_seg_len)

        def attn_branch(args):
            h_, kc_, vc_ = args
            hh = L.apply_norm(sp["ln1"], h_, cfg.norm)
            q, k, v = L.attn_qkv(sp["attn"], cfg, hh, positions)
            attn = L.packed_prefill_attention(
                q, k, v, seg_ids, pos, seg_starts, seg_lens,
                row_len=max_seg_len)
            h2 = h_ + L.attn_out(sp["attn"], h_.dtype, attn)
            hh2 = L.apply_norm(sp["ln2"], h2, cfg.norm)
            h2 = h2 + L.apply_mlp(sp["mlp"], hh2)
            j = jnp.minimum(idx // cfg.attn_every, na - 1)
            kc_ = jax.lax.dynamic_update_slice_in_dim(kc_, k, j, axis=0)
            vc_ = jax.lax.dynamic_update_slice_in_dim(vc_, v, j, axis=0)
            return h2, kc_, vc_

        h, kc, vc = jax.lax.cond(
            (idx + 1) % cfg.attn_every == 0, attn_branch,
            lambda args: args, (h, kc, vc))
        return (h, kc, vc), (states, tails)

    kv_shape = (na, t, cfg.num_kv_heads, cfg.resolved_head_dim)
    kc0 = jnp.zeros(kv_shape, dtype)
    vc0 = jnp.zeros(kv_shape, dtype)
    (x, kc, vc), (states, convs) = jax.lax.scan(
        body, (x, kc0, vc0), (params["layers"], jnp.arange(cfg.num_layers)))
    last = jnp.clip(seg_starts + seg_lens - 1, 0, t - 1)
    xl = L.apply_norm(params["final_norm"], x[0, last], cfg.norm)
    logits = L.unembed(params["embed"], xl, cfg)
    return logits, {"ssm": states, "conv": convs, "attn_k": kc, "attn_v": vc,
                    "pos": seg_lens.astype(jnp.int32)}


def decode_step(params, cfg, token, cache):
    dtype = jnp.dtype(cfg.dtype)
    x = L.embed_tokens(params["embed"], token, dtype)
    pos = jnp.broadcast_to(jnp.asarray(cache["pos"], jnp.int32), token.shape)
    update_attend = L.decode_index(pos, cache, "attn_k")
    positions = pos
    sp = params["shared_attn"]
    na = n_attn_blocks(cfg)

    def body(carry, xs):
        h, kc, vc = carry
        lp, state, conv, idx = xs
        h, (state, conv) = ssm.mamba_block_decode(lp, cfg, h, state, conv)

        def attn_branch(args):
            h_, kc_, vc_ = args
            j = jnp.minimum(idx // cfg.attn_every, na - 1)
            hh = L.apply_norm(sp["ln1"], h_, cfg.norm)
            q, k, v = L.attn_qkv(sp["attn"], cfg, hh[:, None, :], positions[:, None])
            q = L.constrain_q_decode(cfg, q[:, 0])
            attn, kc_, vc_ = update_attend(q, k, v, kc_, vc_, j)
            h2 = h_ + L.attn_out(sp["attn"], h_.dtype, attn)
            hh2 = L.apply_norm(sp["ln2"], h2, cfg.norm)
            h2 = h2 + L.apply_mlp(sp["mlp"], hh2)
            return h2, kc_, vc_

        h, kc, vc = jax.lax.cond(
            (idx + 1) % cfg.attn_every == 0, attn_branch,
            lambda args: args, (h, kc, vc))
        return (h, kc, vc), (state, conv)

    (x, kc, vc), (states, convs) = jax.lax.scan(
        body, (x, cache["attn_k"], cache["attn_v"]),
        (params["layers"], cache["ssm"], cache["conv"],
         jnp.arange(cfg.num_layers)))
    x = L.apply_norm(params["final_norm"], x, cfg.norm)
    logits = L.unembed(params["embed"], x, cfg)
    return logits, L.carry_cache_meta(
        {"ssm": states, "conv": convs, "attn_k": kc, "attn_v": vc,
         "pos": pos + 1}, cache)
