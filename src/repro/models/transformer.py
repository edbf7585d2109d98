"""Dense / MoE / early-fusion-VLM decoder-only transformer.

One code path serves olmo-1b, yi-9b, qwen2-0.5b, deepseek-7b (dense),
phi3.5-moe + granite-moe (``cfg.num_experts > 0``) and chameleon-34b
(early-fusion: VQ image tokens share the vocab, so the backbone is identical).

Layers are ``jax.lax.scan``-ned over stacked params: HLO size and compile
time are depth-independent.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models.moe import apply_moe, moe_plan


# --------------------------------------------------------------------------
# plans
# --------------------------------------------------------------------------
def layer_plan(cfg) -> dict:
    p = {
        "ln1": L.norm_plan(cfg.d_model, cfg.norm),
        "attn": L.attn_plan(cfg),
        "ln2": L.norm_plan(cfg.d_model, cfg.norm),
    }
    if cfg.num_experts:
        p["moe"] = moe_plan(cfg)
    else:
        p["mlp"] = L.mlp_plan(cfg)
    return p


def plan(cfg) -> dict:
    return {
        "embed": L.embed_plan(cfg),
        "layers": L.stack_plan(layer_plan(cfg), cfg.num_layers),
        "final_norm": L.norm_plan(cfg.d_model, cfg.norm),
    }


def init(key, cfg, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "embed": L.init_from_plan(k1, L.embed_plan(cfg), dtype),
        "layers": L.init_stacked(k2, layer_plan(cfg), cfg.num_layers, dtype),
        "final_norm": L.init_from_plan(k3, L.norm_plan(cfg.d_model, cfg.norm), dtype),
    }


# --------------------------------------------------------------------------
# full-sequence forward (training / prefill compute)
# --------------------------------------------------------------------------
def _block(cfg, lp, x, positions, window: int):
    h = L.apply_norm(lp["ln1"], x, cfg.norm)
    q, k, v = L.attn_qkv(lp["attn"], cfg, h, positions)
    attn = L.cp_attention(cfg, q, k, v, causal=True, window=window)
    x = x + L.attn_out(lp["attn"], x.dtype, attn)

    h = L.apply_norm(lp["ln2"], x, cfg.norm)
    if cfg.num_experts:
        y, aux = apply_moe(lp["moe"], cfg, h)
    else:
        y, aux = L.apply_mlp(lp["mlp"], h), {"load_balance_loss": jnp.float32(0.0),
                                             "dropped_fraction": jnp.float32(0.0)}
    return x + y, aux


def forward(params, cfg, tokens, *, remat: bool = False) -> Tuple[jax.Array, dict]:
    """tokens: (B, S) int32 -> logits (B, S, V) plus aux losses."""
    dtype = jnp.dtype(cfg.dtype)
    x = L.embed_tokens(params["embed"], tokens, dtype)
    positions = jnp.arange(tokens.shape[1])[None, :]
    window = cfg.sliding_window

    from repro.utils.sharding import maybe_constrain

    def body(carry, lp):
        y, aux = _block(cfg, lp, carry, positions, window)
        # Megatron-SP style: the remat-saved per-layer carry is sharded on
        # d_model; XLA inserts AG/RS around the attention/mlp einsums.
        y = maybe_constrain(y, "batch", None, "act_embed")
        return y, aux

    if remat:
        body = jax.checkpoint(body)
    x, auxes = jax.lax.scan(body, x, params["layers"])
    x = L.apply_norm(params["final_norm"], x, cfg.norm)
    logits = L.unembed(params["embed"], x, cfg)
    aux = jax.tree.map(jnp.mean, auxes)
    return logits, aux


# --------------------------------------------------------------------------
# KV-cache serving
# --------------------------------------------------------------------------
# cache leaves that live in the shared page pool when the cache is paged
# (everything else — here just "pos" — stays per-row)
PAGED_KEYS = ("k", "v")


def cache_plan(cfg, batch: int, cache_len: int) -> dict:
    lcfg = (cfg.num_layers, batch, cache_len, cfg.num_kv_heads, cfg.resolved_head_dim)
    spec = L.kv_cache_spec(cfg)
    return {
        "k": L.ParamDef(lcfg, spec, "zeros"),
        "v": L.ParamDef(lcfg, spec, "zeros"),
        # per-sequence positions/lengths: ragged batches + slot reuse
        "pos": L.ParamDef((batch,), None, "zeros"),
    }


def init_cache(cfg, batch: int, cache_len: int, dtype=None):
    dtype = dtype or jnp.dtype(cfg.dtype)
    cp = cache_plan(cfg, batch, cache_len)
    return {
        "k": jnp.zeros(cp["k"].shape, dtype),
        "v": jnp.zeros(cp["v"].shape, dtype),
        "pos": jnp.zeros((batch,), jnp.int32),
    }


def paged_cache_plan(cfg, batch: int, num_pages: int, page_size: int,
                     max_pages: int) -> dict:
    """Block-table paged layout: K/V live in a shared (num_pages,
    page_size) pool; each row maps logical pages to physical via its
    ``block_tables`` row (see ``repro.serving.kv_cache``)."""
    kv = L.paged_kv_plan(cfg, cfg.num_layers, num_pages, page_size)
    return {
        "k": kv,
        "v": kv,
        "block_tables": L.ParamDef((batch, max_pages), None, "zeros"),
        "pos": L.ParamDef((batch,), None, "zeros"),
    }


def init_paged_cache(cfg, batch: int, num_pages: int, page_size: int,
                     max_pages: int, dtype=None):
    dtype = dtype or jnp.dtype(cfg.dtype)
    cp = paged_cache_plan(cfg, batch, num_pages, page_size, max_pages)
    return {
        "k": jnp.zeros(cp["k"].shape, dtype),
        "v": jnp.zeros(cp["v"].shape, dtype),
        "block_tables": jnp.zeros((batch, max_pages), jnp.int32),
        "pos": jnp.zeros((batch,), jnp.int32),
    }


def prefill(params, cfg, tokens, cache_len: int):
    """Run the prompt through the model, building a fresh KV cache.

    Returns logits of the *last* position (B, V) and the cache.
    """
    dtype = jnp.dtype(cfg.dtype)
    b, s = tokens.shape
    x = L.embed_tokens(params["embed"], tokens, dtype)
    positions = jnp.arange(s)[None, :]
    window = cfg.sliding_window

    def body(carry, lp):
        h = L.apply_norm(lp["ln1"], carry, cfg.norm)
        q, k, v = L.attn_qkv(lp["attn"], cfg, h, positions)
        attn = L.cp_attention(cfg, q, k, v, causal=True, window=window)
        x1 = carry + L.attn_out(lp["attn"], carry.dtype, attn)
        h2 = L.apply_norm(lp["ln2"], x1, cfg.norm)
        if cfg.num_experts:
            y, _ = apply_moe(lp["moe"], cfg, h2)
        else:
            y = L.apply_mlp(lp["mlp"], h2)
        # write last ``cache_len`` keys into the (possibly ring) cache
        if s <= cache_len:
            k_out = jnp.zeros((b, cache_len) + k.shape[2:], k.dtype).at[:, :s].set(k)
            v_out = jnp.zeros((b, cache_len) + v.shape[2:], v.dtype).at[:, :s].set(v)
        else:  # sliding-window cache smaller than prompt: keep the tail
            k_out, v_out = k[:, s - cache_len:], v[:, s - cache_len:]
        return x1 + y, (k_out, v_out)

    x, (ks, vs) = jax.lax.scan(body, x, params["layers"])
    x = L.apply_norm(params["final_norm"], x[:, -1], cfg.norm)
    logits = L.unembed(params["embed"], x, cfg)
    new_cache = {"k": ks, "v": vs, "pos": jnp.full((b,), s, jnp.int32)}
    return logits, new_cache


def prefill_packed(params, cfg, packed, max_seg_len: int):
    """Packed ragged prefill: a whole admission batch of variable-length
    prompts concatenated into ONE (1, total_tokens) row.

    ``packed`` carries ``tokens`` (1, T), ``seg_ids`` (T,) non-decreasing
    int32 (padding tokens = S), ``seg_starts``/``seg_lens`` (S,). Returns
    (per-segment last-token logits (S, V), a PACKED cache: per-token K/V
    (layers, T, KV, D) in packed order — the engine scatters each
    segment's tokens straight into its slot's pages — and ``pos`` =
    seg_lens). Unlike ``prefill`` there is no padding to a common prompt
    length: every non-attention op runs on sum(lens) tokens, and the
    attention is segment-masked (see ``layers.packed_prefill_attention``).

    MoE caveat: expert-capacity dropping is computed per dispatch group,
    so a packed MoE prefill can drop different tokens than per-request
    prefills of the same prompts (dense families are bit-exact)."""
    dtype = jnp.dtype(cfg.dtype)
    tokens = packed["tokens"]
    seg_ids, seg_starts = packed["seg_ids"], packed["seg_starts"]
    seg_lens = packed["seg_lens"]
    b, t = tokens.shape
    x = L.embed_tokens(params["embed"], tokens, dtype)
    pos = L.packed_positions(seg_ids, seg_starts)
    positions = pos[None, :]
    window = cfg.sliding_window

    def body(carry, lp):
        h = L.apply_norm(lp["ln1"], carry, cfg.norm)
        q, k, v = L.attn_qkv(lp["attn"], cfg, h, positions)
        attn = L.packed_prefill_attention(q, k, v, seg_ids, pos,
                                          seg_starts, seg_lens,
                                          row_len=max_seg_len, window=window)
        x1 = carry + L.attn_out(lp["attn"], carry.dtype, attn)
        h2 = L.apply_norm(lp["ln2"], x1, cfg.norm)
        if cfg.num_experts:
            y, _ = apply_moe(lp["moe"], cfg, h2)
        else:
            y = L.apply_mlp(lp["mlp"], h2)
        return x1 + y, (k[0], v[0])

    x, (ks, vs) = jax.lax.scan(body, x, params["layers"])
    last = jnp.clip(seg_starts + seg_lens - 1, 0, t - 1)
    xl = L.apply_norm(params["final_norm"], x[0, last], cfg.norm)
    logits = L.unembed(params["embed"], xl, cfg)
    return logits, {"k": ks, "v": vs, "pos": seg_lens.astype(jnp.int32)}


def prefill_chunk(params, cfg, packed, cache, max_seg_len: int):
    """Incremental chunked prefill: score a packed batch of NEW token
    segments against the K/V their sequences already hold in the paged
    pool — each chunk token attends its slot's resident history (through
    its block-table row) plus the chunk's earlier tokens causally, so a
    continuation costs O(chunk) attention instead of recomputing the
    whole prefix. The same dispatch powers speculative-decoding
    verification: the k draft tokens are the chunk, and every position's
    argmax is returned so the engine can score the draft on host.

    ``packed`` carries the usual ``tokens`` (1, T) / ``seg_ids`` (T,) /
    ``seg_starts``/``seg_lens`` (S,) plus ``seg_slots`` (S,) — the cache
    row each segment's history lives in (padding = n_rows, clamped) —
    and ``hist_lens`` (S,) — tokens already resident per segment
    (padding = 0). ``cache`` is the engine's paged slot cache, READ
    ONLY: (layers, KV, P, page_size, D) pools + (n_rows, max_pages)
    ``block_tables``. Returns (per-segment last-position logits (S, V),
    per-token argmax (T,) int32, a packed cache {k/v: (layers, T, KV, D),
    pos: hist + seg_lens}) — the engine scatters the chunk's K/V into
    pages afterwards via the same segment scatter admissions use.

    On the jnp fallback every chunk position runs the exact masked-decode
    attention body (see ``layers._masked_chunk_attention``), so chunk
    logits are bit-identical to the decode steps they replace."""
    dtype = jnp.dtype(cfg.dtype)
    tokens = packed["tokens"]
    seg_ids, seg_starts = packed["seg_ids"], packed["seg_starts"]
    seg_lens = packed["seg_lens"]
    seg_slots = packed["seg_slots"]
    hist = jnp.asarray(packed["hist_lens"], jnp.int32)
    b, t = tokens.shape
    s = seg_starts.shape[0]
    x = L.embed_tokens(params["embed"], tokens, dtype)
    local = L.packed_positions(seg_ids, seg_starts)
    hist_t = jnp.where(seg_ids < s, hist[jnp.minimum(seg_ids, s - 1)], 0)
    positions = (local + hist_t)[None, :]
    n_rows = cache["block_tables"].shape[0]
    tables = cache["block_tables"][jnp.clip(seg_slots, 0, n_rows - 1)]

    # the pools stay out of the scan's xs (each layer's slice would be a
    # whole-layer copy): every layer reads the stacked pools at its index
    kp, vp = cache["k"], cache["v"]

    def body(carry, xs):
        lp, idx = xs
        h = L.apply_norm(lp["ln1"], carry, cfg.norm)
        q, k, v = L.attn_qkv(lp["attn"], cfg, h, positions)
        qr = L.segments_to_rows(q[0], seg_starts, seg_lens, max_seg_len)
        kr = L.segments_to_rows(k[0], seg_starts, seg_lens, max_seg_len)
        vr = L.segments_to_rows(v[0], seg_starts, seg_lens, max_seg_len)
        ar = L.paged_chunk_attention(qr, kp, vp, kr, vr, tables, hist,
                                     seg_lens, idx)
        attn = L.rows_to_segments(ar, seg_ids, local)[None]
        x1 = carry + L.attn_out(lp["attn"], carry.dtype, attn)
        h2 = L.apply_norm(lp["ln2"], x1, cfg.norm)
        if cfg.num_experts:
            y, _ = apply_moe(lp["moe"], cfg, h2)
        else:
            y = L.apply_mlp(lp["mlp"], h2)
        return x1 + y, (k[0], v[0])

    x, (ks, vs) = jax.lax.scan(
        body, x, (params["layers"], jnp.arange(cfg.num_layers)))
    xl = L.apply_norm(params["final_norm"], x[0], cfg.norm)
    logits_all = L.unembed(params["embed"], xl, cfg)           # (T, V)
    tok_argmax = jnp.argmax(logits_all, -1).astype(jnp.int32)
    last = jnp.clip(seg_starts + seg_lens - 1, 0, t - 1)
    seg_logits = logits_all[last]
    return seg_logits, tok_argmax, {
        "k": ks, "v": vs, "pos": (hist + seg_lens).astype(jnp.int32)}


def decode_step(params, cfg, token, cache) -> Tuple[jax.Array, dict]:
    """token: (B,) int32; one autoregressive step against the KV cache.

    ``cache["pos"]`` is a per-sequence (B,) vector: each row writes its new
    K/V at its own ring slot and attends only up to its own length, so a
    mixed-length (ragged) batch never pays for the longest row and vacant
    continuous-batching slots cost nothing but the row's lane.

    The cache is threaded through the layer scan as CARRY and written at
    the layer index (``layers.decode_index``) — a scan-over-(xs -> ys)
    cache double-buffers (measured +2x cache HBM on deepseek decode_32k);
    the carried buffer updates in place and aliases with the donated
    input. A ring cache slices its layer out and updates it back.

    A cache built by ``init_paged_cache`` (it carries ``block_tables``; a
    static pytree property, so this is a trace-time branch, not a runtime
    one) stores K/V in the shared page pool (layers, KV, P, page_size, D)
    instead, and the step never slices it: each row's new entry is
    scattered as KV rows of (D,) at (layer, head, block_tables[b, pos //
    page_size], pos % page_size), and ``paged_decode_attention`` reads
    the stacked pool at the layer index. Same scan-carry structure, same
    per-sequence raggedness — only the storage indexing differs.
    """
    dtype = jnp.dtype(cfg.dtype)
    x = L.embed_tokens(params["embed"], token, dtype)          # (B, d)
    pos = jnp.broadcast_to(jnp.asarray(cache["pos"], jnp.int32), token.shape)
    positions = pos
    update_attend = L.decode_index(pos, cache, "k")

    def body(carry, xs):
        h0, kfull, vfull = carry
        lp, idx = xs
        h = L.apply_norm(lp["ln1"], h0, cfg.norm)
        q, k, v = L.attn_qkv(lp["attn"], cfg, h[:, None, :], positions[:, None])
        q = L.constrain_q_decode(cfg, q[:, 0])                 # (B, H, hd)
        attn, kfull, vfull = update_attend(q, k, v, kfull, vfull, idx,
                                           window=cfg.sliding_window)
        x1 = h0 + L.attn_out(lp["attn"], h0.dtype, attn)
        h2 = L.apply_norm(lp["ln2"], x1, cfg.norm)
        if cfg.num_experts:
            y, _ = apply_moe(lp["moe"], cfg, h2)
        else:
            y = L.apply_mlp(lp["mlp"], h2)
        return (x1 + y, kfull, vfull), None

    (x, ks, vs), _ = jax.lax.scan(
        body, (x, cache["k"], cache["v"]),
        (params["layers"], jnp.arange(cfg.num_layers)))
    x = L.apply_norm(params["final_norm"], x, cfg.norm)
    logits = L.unembed(params["embed"], x, cfg)
    return logits, L.carry_cache_meta({"k": ks, "v": vs, "pos": pos + 1},
                                      cache)
