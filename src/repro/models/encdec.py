"""Whisper-style encoder-decoder transformer backbone.

The mel-spectrogram + conv feature extractor is a STUB per the brief:
``batch["enc_embeds"]`` carries precomputed frame embeddings
(B, encoder_seq, d_model). Learned positional embeddings; decoder layers use
self-attention (causal, KV-cached) + cross-attention over the encoder output
(cross K/V computed once at prefill).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models.layers import ParamDef

MAX_DEC_POS = 32_768


def enc_layer_plan(cfg) -> dict:
    return {
        "ln1": L.norm_plan(cfg.d_model, cfg.norm),
        "attn": L.attn_plan(cfg),
        "ln2": L.norm_plan(cfg.d_model, cfg.norm),
        "mlp": L.mlp_plan(cfg),
    }


def dec_layer_plan(cfg) -> dict:
    return {
        "ln1": L.norm_plan(cfg.d_model, cfg.norm),
        "self_attn": L.attn_plan(cfg),
        "ln2": L.norm_plan(cfg.d_model, cfg.norm),
        "cross_attn": L.attn_plan(cfg),
        "ln3": L.norm_plan(cfg.d_model, cfg.norm),
        "mlp": L.mlp_plan(cfg),
    }


def plan(cfg) -> dict:
    return {
        "embed": L.embed_plan(cfg),
        "enc_pos": ParamDef((cfg.encoder_seq, cfg.d_model), (None, "embed")),
        "dec_pos": ParamDef((MAX_DEC_POS, cfg.d_model), (None, "embed")),
        "enc_layers": L.stack_plan(enc_layer_plan(cfg), cfg.encoder_layers),
        "enc_final": L.norm_plan(cfg.d_model, cfg.norm),
        "layers": L.stack_plan(dec_layer_plan(cfg), cfg.num_layers),
        "final_norm": L.norm_plan(cfg.d_model, cfg.norm),
    }


def init(key, cfg, dtype=jnp.float32):
    ks = jax.random.split(key, 7)
    return {
        "embed": L.init_from_plan(ks[0], L.embed_plan(cfg), dtype),
        "enc_pos": L.init_from_plan(
            ks[1], ParamDef((cfg.encoder_seq, cfg.d_model), None), dtype),
        "dec_pos": L.init_from_plan(
            ks[2], ParamDef((MAX_DEC_POS, cfg.d_model), None), dtype),
        "enc_layers": L.init_stacked(ks[3], enc_layer_plan(cfg), cfg.encoder_layers, dtype),
        "enc_final": L.init_from_plan(ks[4], L.norm_plan(cfg.d_model, cfg.norm), dtype),
        "layers": L.init_stacked(ks[5], dec_layer_plan(cfg), cfg.num_layers, dtype),
        "final_norm": L.init_from_plan(ks[6], L.norm_plan(cfg.d_model, cfg.norm), dtype),
    }


# --------------------------------------------------------------------------
# encoder
# --------------------------------------------------------------------------
def encode(params, cfg, enc_embeds):
    dtype = jnp.dtype(cfg.dtype)
    s = enc_embeds.shape[1]
    x = enc_embeds.astype(dtype) + params["enc_pos"][:s].astype(dtype)
    positions = jnp.arange(s)[None, :]

    def body(carry, lp):
        h = L.apply_norm(lp["ln1"], carry, cfg.norm)
        q, k, v = L.attn_qkv(lp["attn"], cfg, h, positions)
        attn = L.cp_attention(cfg, q, k, v, causal=False)
        x1 = carry + L.attn_out(lp["attn"], carry.dtype, attn)
        h2 = L.apply_norm(lp["ln2"], x1, cfg.norm)
        return x1 + L.apply_mlp(lp["mlp"], h2), None

    x, _ = jax.lax.scan(body, x, params["enc_layers"])
    return L.apply_norm(params["enc_final"], x, cfg.norm)


def _cross_kv(lp, cfg, enc_out):
    k = jnp.einsum("bsd,dhk->bshk", enc_out, lp["cross_attn"]["wk"].astype(enc_out.dtype))
    v = jnp.einsum("bsd,dhk->bshk", enc_out, lp["cross_attn"]["wv"].astype(enc_out.dtype))
    if cfg.qkv_bias:
        k = k + lp["cross_attn"]["bk"].astype(enc_out.dtype)
        v = v + lp["cross_attn"]["bv"].astype(enc_out.dtype)
    return k, v


def _dec_block(lp, cfg, x, positions, enc_out):
    h = L.apply_norm(lp["ln1"], x, cfg.norm)
    q, k, v = L.attn_qkv(lp["self_attn"], cfg, h, positions)
    attn = L.cp_attention(cfg, q, k, v, causal=True)
    x = x + L.attn_out(lp["self_attn"], x.dtype, attn)

    h = L.apply_norm(lp["ln2"], x, cfg.norm)
    qc = jnp.einsum("bsd,dhk->bshk", h, lp["cross_attn"]["wq"].astype(x.dtype))
    kc, vc = _cross_kv(lp, cfg, enc_out)
    cross = L.cp_attention(cfg, qc, kc, vc, causal=False)
    x = x + L.attn_out(lp["cross_attn"], x.dtype, cross)

    h = L.apply_norm(lp["ln3"], x, cfg.norm)
    return x + L.apply_mlp(lp["mlp"], h)


def forward(params, cfg, batch_tokens, enc_embeds, *, remat: bool = False):
    dtype = jnp.dtype(cfg.dtype)
    enc_out = encode(params, cfg, enc_embeds)
    b, s = batch_tokens.shape
    x = (L.embed_tokens(params["embed"], batch_tokens, dtype)
         + params["dec_pos"][:s].astype(dtype))
    positions = jnp.arange(s)[None, :]

    from repro.utils.sharding import maybe_constrain

    def body(carry, lp):
        y = _dec_block(lp, cfg, carry, positions, enc_out)
        return maybe_constrain(y, "batch", None, "act_embed"), None

    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, params["layers"])
    x = L.apply_norm(params["final_norm"], x, cfg.norm)
    logits = L.unembed(params["embed"], x, cfg)
    aux = {"load_balance_loss": jnp.float32(0.0),
           "dropped_fraction": jnp.float32(0.0)}
    return logits, aux


# --------------------------------------------------------------------------
# serving
# --------------------------------------------------------------------------
# decoder self-attention KV pages; the cross K/V is a fixed encoder_seq-long
# read-only block per request, so it stays a per-slot dense leaf
PAGED_KEYS = ("k", "v")


def cache_plan(cfg, batch: int, cache_len: int) -> dict:
    hd = cfg.resolved_head_dim
    kv_shape = (cfg.num_layers, batch, cache_len, cfg.num_kv_heads, hd)
    cross_shape = (cfg.num_layers, batch, cfg.encoder_seq, cfg.num_kv_heads, hd)
    spec = L.kv_cache_spec(cfg)
    return {
        "k": ParamDef(kv_shape, spec, "zeros"),
        "v": ParamDef(kv_shape, spec, "zeros"),
        "cross_k": ParamDef(cross_shape, spec, "zeros"),
        "cross_v": ParamDef(cross_shape, spec, "zeros"),
        # per-sequence positions: ragged batches + slot reuse
        "pos": ParamDef((batch,), None, "zeros"),
    }


def init_cache(cfg, batch: int, cache_len: int, dtype=None):
    dtype = dtype or jnp.dtype(cfg.dtype)
    cp = cache_plan(cfg, batch, cache_len)
    return {k: (jnp.zeros((batch,), jnp.int32) if k == "pos"
                else jnp.zeros(cp[k].shape, dtype))
            for k in cp}


def paged_cache_plan(cfg, batch: int, num_pages: int, page_size: int,
                     max_pages: int) -> dict:
    hd = cfg.resolved_head_dim
    kv = L.paged_kv_plan(cfg, cfg.num_layers, num_pages, page_size)
    cross_shape = (cfg.num_layers, batch, cfg.encoder_seq, cfg.num_kv_heads, hd)
    return {
        "k": kv,
        "v": kv,
        "cross_k": ParamDef(cross_shape, L.kv_cache_spec(cfg), "zeros"),
        "cross_v": ParamDef(cross_shape, L.kv_cache_spec(cfg), "zeros"),
        "block_tables": ParamDef((batch, max_pages), None, "zeros"),
        "pos": ParamDef((batch,), None, "zeros"),
    }


def init_paged_cache(cfg, batch: int, num_pages: int, page_size: int,
                     max_pages: int, dtype=None):
    dtype = dtype or jnp.dtype(cfg.dtype)
    cp = paged_cache_plan(cfg, batch, num_pages, page_size, max_pages)
    return {k: (jnp.zeros(cp[k].shape, jnp.int32)
                if k in ("pos", "block_tables")
                else jnp.zeros(cp[k].shape, dtype))
            for k in cp}


def prefill(params, cfg, tokens, cache_len: int, enc_embeds):
    """Encode the (stub) audio, cache cross K/V, run the decoder prompt."""
    dtype = jnp.dtype(cfg.dtype)
    b, s = tokens.shape
    enc_out = encode(params, cfg, enc_embeds)
    x = (L.embed_tokens(params["embed"], tokens, dtype)
         + params["dec_pos"][:s].astype(dtype))
    positions = jnp.arange(s)[None, :]

    def body(carry, lp):
        h = L.apply_norm(lp["ln1"], carry, cfg.norm)
        q, k, v = L.attn_qkv(lp["self_attn"], cfg, h, positions)
        attn = L.cp_attention(cfg, q, k, v, causal=True)
        x1 = carry + L.attn_out(lp["self_attn"], carry.dtype, attn)

        h2 = L.apply_norm(lp["ln2"], x1, cfg.norm)
        qc = jnp.einsum("bsd,dhk->bshk", h2, lp["cross_attn"]["wq"].astype(x1.dtype))
        kc, vc = _cross_kv(lp, cfg, enc_out)
        cross = L.cp_attention(cfg, qc, kc, vc, causal=False)
        x2 = x1 + L.attn_out(lp["cross_attn"], x1.dtype, cross)

        h3 = L.apply_norm(lp["ln3"], x2, cfg.norm)
        x3 = x2 + L.apply_mlp(lp["mlp"], h3)
        k_out = jnp.zeros((b, cache_len) + k.shape[2:], k.dtype).at[:, :s].set(k)
        v_out = jnp.zeros((b, cache_len) + v.shape[2:], v.dtype).at[:, :s].set(v)
        return x3, (k_out, v_out, kc, vc)

    x, (ks, vs, cks, cvs) = jax.lax.scan(body, x, params["layers"])
    x = L.apply_norm(params["final_norm"], x[:, -1], cfg.norm)
    logits = L.unembed(params["embed"], x, cfg)
    return logits, {"k": ks, "v": vs, "cross_k": cks, "cross_v": cvs,
                    "pos": jnp.full((b,), s, jnp.int32)}


def prefill_packed(params, cfg, packed, max_seg_len: int):
    """Packed ragged prefill: only the DECODER side packs. The encoder
    runs densely over the per-segment ``enc_embeds`` stack (S, enc_seq,
    d) — encoder frames are fixed-length per request, there is nothing
    ragged to pack — and each packed decoder token cross-attends its own
    segment's encoder output (``layers.packed_cross_attention``).
    Decoder self-attention K/V comes back in packed per-token order
    (layers, T, KV, D) for the engine's direct-to-pages scatter; the
    cross K/V stays a per-segment dense block, exactly like the per-slot
    layout it is scattered into."""
    tokens = packed["tokens"]
    seg_ids, seg_starts = packed["seg_ids"], packed["seg_starts"]
    seg_lens = packed["seg_lens"]
    dtype = jnp.dtype(cfg.dtype)
    b, t = tokens.shape
    enc_out = encode(params, cfg, packed["enc_embeds"])   # (S, enc_seq, d)
    pos = L.packed_positions(seg_ids, seg_starts)
    positions = pos[None, :]
    x = (L.embed_tokens(params["embed"], tokens, dtype)
         + params["dec_pos"][pos][None].astype(dtype))

    def body(carry, lp):
        h = L.apply_norm(lp["ln1"], carry, cfg.norm)
        q, k, v = L.attn_qkv(lp["self_attn"], cfg, h, positions)
        attn = L.packed_prefill_attention(q, k, v, seg_ids, pos,
                                          seg_starts, seg_lens,
                                          row_len=max_seg_len)
        x1 = carry + L.attn_out(lp["self_attn"], carry.dtype, attn)

        h2 = L.apply_norm(lp["ln2"], x1, cfg.norm)
        qc = jnp.einsum("bsd,dhk->bshk", h2,
                        lp["cross_attn"]["wq"].astype(x1.dtype))
        kc, vc = _cross_kv(lp, cfg, enc_out)              # (S, enc, KV, hd)
        cross = L.packed_cross_attention(qc, kc, vc, seg_ids, pos,
                                         seg_starts, seg_lens,
                                         row_len=max_seg_len)
        x2 = x1 + L.attn_out(lp["cross_attn"], x1.dtype, cross)

        h3 = L.apply_norm(lp["ln3"], x2, cfg.norm)
        x3 = x2 + L.apply_mlp(lp["mlp"], h3)
        return x3, (k[0], v[0], kc, vc)

    x, (ks, vs, cks, cvs) = jax.lax.scan(body, x, params["layers"])
    last = jnp.clip(seg_starts + seg_lens - 1, 0, t - 1)
    xl = L.apply_norm(params["final_norm"], x[0, last], cfg.norm)
    logits = L.unembed(params["embed"], xl, cfg)
    return logits, {"k": ks, "v": vs, "cross_k": cks, "cross_v": cvs,
                    "pos": seg_lens.astype(jnp.int32)}


def decode_step(params, cfg, token, cache):
    """Self-attention cache is carried + updated in place; the read-only
    cross K/V streams through the scan as xs (no double-buffering)."""
    dtype = jnp.dtype(cfg.dtype)
    pos = jnp.broadcast_to(jnp.asarray(cache["pos"], jnp.int32), token.shape)
    update_attend = L.decode_index(pos, cache, "k")
    x = (L.embed_tokens(params["embed"], token, dtype)
         + params["dec_pos"][pos].astype(dtype))
    positions = pos
    enc_len = cache["cross_k"].shape[2]

    def body(carry, xs):
        h0, kfull, vfull = carry
        lp, ck, cv, idx = xs
        h = L.apply_norm(lp["ln1"], h0, cfg.norm)
        q, k, v = L.attn_qkv(lp["self_attn"], cfg, h[:, None, :], positions[:, None])
        q = L.constrain_q_decode(cfg, q[:, 0])
        attn, kfull, vfull = update_attend(q, k, v, kfull, vfull, idx)
        x1 = h0 + L.attn_out(lp["self_attn"], h0.dtype, attn)

        h2 = L.apply_norm(lp["ln2"], x1, cfg.norm)
        qc = jnp.einsum("bd,dhk->bhk", h2, lp["cross_attn"]["wq"].astype(x1.dtype))
        qc = L.constrain_q_decode(cfg, qc)
        cross = L.decode_attention(qc, ck, cv, enc_len)
        x2 = x1 + L.attn_out(lp["cross_attn"], x1.dtype, cross)

        h3 = L.apply_norm(lp["ln3"], x2, cfg.norm)
        x3 = x2 + L.apply_mlp(lp["mlp"], h3)
        return (x3, kfull, vfull), None

    (x, ks, vs), _ = jax.lax.scan(
        body, (x, cache["k"], cache["v"]),
        (params["layers"], cache["cross_k"], cache["cross_v"],
         jnp.arange(cfg.num_layers)))
    x = L.apply_norm(params["final_norm"], x, cfg.norm)
    logits = L.unembed(params["embed"], x, cfg)
    return logits, L.carry_cache_meta(
        {"k": ks, "v": vs, "cross_k": cache["cross_k"],
         "cross_v": cache["cross_v"], "pos": pos + 1}, cache)
