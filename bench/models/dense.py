"""Plain reference of a dense decoder-only transformer in the OLMo-1B form
(arXiv:2402.00838): non-parametric LayerNorm before attention and MLP, full
multi-head attention with rotary embeddings (rotate-half pairs), SwiGLU
MLP, tied input/output embeddings, no biases.

float32 at "highest" matmul precision, one sequence at a time, no cache,
no kernels, no batching. Departures from the published model: none in the
forward pass; the weights are random (std 0.02) rather than trained.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from models.common import gaps, make_weights, matmul


def padded_vocab(v: int) -> int:
    """Rows of the embedding table the engine holds: the vocabulary padded
    to a multiple of 256 unless it already divides by 16."""
    return v if v % 16 == 0 else -(-v // 256) * 256


def weight_spec(m: Dict) -> Dict:
    """(shape, init, std) of every weight, stacked over layers."""
    nl, d = m["num_hidden_layers"], m["hidden_size"]
    h, kv = m["num_attention_heads"], m["num_key_value_heads"]
    hd, ff = d // h, m["intermediate_size"]

    def normal(*shape):
        return (shape, "normal", 0.02)
    return {
        "embed": {"embedding": normal(padded_vocab(m["vocab_size"]), d)},
        "layers": {
            "ln1": {}, "ln2": {},
            "attn": {"wq": normal(nl, d, h, hd), "wk": normal(nl, d, kv, hd),
                     "wv": normal(nl, d, kv, hd), "wo": normal(nl, h, hd, d)},
            "mlp": {"wi_gate": normal(nl, d, ff), "wi_up": normal(nl, d, ff),
                    "wo": normal(nl, ff, d)},
        },
        "final_norm": {},
    }


def weights(m: Dict, seed: int, dtype) -> Dict:
    return make_weights(weight_spec(m), seed, dtype)


def _ln(x, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps)


def _rope(x, theta):
    """x (L, H, D): rotate the pairs (i, i + D/2) by position * freq_i."""
    d = x.shape[-1]
    half = d // 2
    freq = 1.0 / (theta ** (np.arange(half, dtype=np.float32) * 2.0 / d))
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def logits(w: Dict, m: Dict, tokens, mode: str = "ref"):
    """tokens (L,) int32 -> (L, vocab_size) float32 logits, position i
    predicting token i + 1."""
    d, h = m["hidden_size"], m["num_attention_heads"]
    kv, hd = m["num_key_value_heads"], m["hidden_size"] // h
    eps, theta, v = m["layer_norm_eps"], m["rope_theta"], m["vocab_size"]
    n = tokens.shape[0]
    emb = w["embed"]["embedding"]
    x = emb[tokens].astype(jnp.float32)
    causal = jnp.tril(jnp.ones((n, n), bool))

    def layer(x, lw):
        a = lw["attn"]
        y = _ln(x, eps)
        q = _rope(matmul(y, a["wq"].reshape(d, h * hd), mode)
                  .reshape(n, h, hd), theta)
        k = _rope(matmul(y, a["wk"].reshape(d, kv * hd), mode)
                  .reshape(n, kv, hd), theta)
        vv = matmul(y, a["wv"].reshape(d, kv * hd), mode).reshape(n, kv, hd)
        k = jnp.repeat(k, h // kv, axis=1)
        vv = jnp.repeat(vv, h // kv, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(hd)
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        o = jnp.einsum("hqk,khd->qhd", p, vv).reshape(n, h * hd)
        x = x + matmul(o, a["wo"].reshape(h * hd, d), mode)
        y = _ln(x, eps)
        f = lw["mlp"]
        g = matmul(y, f["wi_gate"], mode)
        u = matmul(y, f["wi_up"], mode)
        x = x + matmul(jax.nn.silu(g) * u, f["wo"], mode)
        return x, None

    with jax.default_matmul_precision("highest"):
        x, _ = jax.lax.scan(layer, x, w["layers"])
        x = _ln(x, eps)
        return matmul(x, emb[:v].T, mode)


def token_gaps(w: Dict, m: Dict, tokens, mode: str):
    """Per position i, the gap under the float32 reference of token i + 1
    as served (``mode`` "ref"), or of the token the int8 control puts
    first there (``mode`` "int8")."""
    ref = logits(w, m, tokens, "ref")
    if mode == "ref":
        nxt = jnp.concatenate([tokens[1:], tokens[-1:]])
    else:
        nxt = jnp.argmax(logits(w, m, tokens, mode), -1).astype(jnp.int32)
    return gaps(ref, nxt)


def flops_per_token(m: Dict, ctx: float) -> float:
    """Model FLOPs of one token attending ``ctx`` positions: 2 per weight
    of every matmul (unembedding included) plus QK and PV."""
    d, nl, ff = m["hidden_size"], m["num_hidden_layers"], m["intermediate_size"]
    h, kv = m["num_attention_heads"], m["num_key_value_heads"]
    hd = d // h
    per_layer = d * (h + 2 * kv) * hd + h * hd * d + 3 * d * ff
    return 2.0 * (nl * per_layer + d * m["vocab_size"]) \
        + 4.0 * nl * h * hd * ctx
