"""Plain reference of a Mamba2 decoder (arXiv:2405.21060, SSD): per layer,
RMSNorm, in-projections to z, x, B, C (one group) and dt, a depthwise
causal convolution of width ``conv_kernel`` over (x, B, C) with SiLU,
dt = softplus(. + dt_bias), the SSD recurrence
``h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t^T``, ``y_t = C_t h_t + D x_t``,
gated RMSNorm ``norm(y * silu(z))`` and the out-projection; tied
embeddings.

Computed in float32 at "highest" matmul precision through the SSD's
quadratic (masked-attention) form over the whole sequence, with no chunks,
no cache and no kernels. Departures from the published model: the
convolution has no bias (the engine's layer has none), and the weights
are random: A_log = log U(1, 16) and dt_bias = softplus^-1(dt) with
log dt ~ U(log 1e-3, log 1e-1), as Mamba2 initialises them, other
matrices normal with std 0.02 (0.2 for the convolution).
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from models.common import gaps, make_weights, matmul
from models.dense import padded_vocab


def _dims(m: Dict):
    d = m["d_model"]
    di = m["expand"] * d
    return (m["n_layer"], d, di, m["d_state"], di // m["headdim"],
            m["headdim"], m["d_conv"])


def weight_spec(m: Dict) -> Dict:
    nl, d, di, n, nh, _, w = _dims(m)
    return {
        "embed": {"embedding": ((padded_vocab(m["vocab_size"]), d),
                                "normal", 0.02)},
        "layers": {
            "norm": {"scale": ((nl, d), "ones", 0.0)},
            "wz": ((nl, d, di), "normal", 0.02),
            "wx": ((nl, d, di), "normal", 0.02),
            "wB": ((nl, d, n), "normal", 0.02),
            "wC": ((nl, d, n), "normal", 0.02),
            "wdt": ((nl, d, nh), "normal", 0.02),
            "dt_bias": ((nl, nh), "custom", 0.0),
            "A_log": ((nl, nh), "custom", 0.0),
            "D": ((nl, nh), "ones", 0.0),
            "conv_x": ((nl, w, di), "normal", 0.2),
            "conv_B": ((nl, w, n), "normal", 0.2),
            "conv_C": ((nl, w, n), "normal", 0.2),
            "gate_norm": {"scale": ((nl, di), "ones", 0.0)},
            "wo": ((nl, di, d), "normal", 0.02),
        },
        "final_norm": {"scale": ((d,), "ones", 0.0)},
    }


def _dt_bias(key, shape):
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                    jnp.log(1e-3), jnp.log(1e-1)))
    return dt + jnp.log(-jnp.expm1(-dt))          # softplus^-1


def _a_log(key, shape):
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))


def weights(m: Dict, seed: int, dtype) -> Dict:
    return make_weights(weight_spec(m), seed, dtype,
                        {"layers/dt_bias": _dt_bias, "layers/A_log": _a_log})


def _rms(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def logits(w: Dict, m: Dict, tokens, mode: str = "ref"):
    """tokens (L,) int32 -> (L, vocab_size) float32 logits."""
    _, d, di, n, nh, p, width = _dims(m)
    eps, v = m["norm_eps"], m["vocab_size"]
    ln = tokens.shape[0]
    emb = w["embed"]["embedding"]
    x = emb[tokens].astype(jnp.float32)
    f32 = jnp.float32
    lower = jnp.tril(jnp.ones((ln, ln), bool))

    def layer(x, lw):
        y = _rms(x, lw["norm"]["scale"].astype(f32), eps)
        z = matmul(y, lw["wz"], mode)
        xbc = jnp.concatenate([matmul(y, lw["wx"], mode),
                               matmul(y, lw["wB"], mode),
                               matmul(y, lw["wC"], mode)], -1)
        dt = jax.nn.softplus(matmul(y, lw["wdt"], mode)
                             + lw["dt_bias"].astype(f32))       # (L, H)
        cw = jnp.concatenate([lw["conv_x"], lw["conv_B"], lw["conv_C"]],
                             -1).astype(f32)                    # (W, C)
        pad = jnp.pad(xbc, ((width - 1, 0), (0, 0)))
        conv = sum(pad[i:i + ln] * cw[i] for i in range(width))
        xbc = jax.nn.silu(conv)
        xs, b, c = xbc[:, :di], xbc[:, di:di + n], xbc[:, di + n:]
        xh = xs.reshape(ln, nh, p)
        a = -jnp.exp(lw["A_log"].astype(f32))                   # (H,)
        cs = jnp.cumsum(dt * a, axis=0)                         # (L, H)
        seg = cs[:, None, :] - cs[None, :, :]                   # (t, s, H)
        decay = jnp.where(lower[..., None], jnp.exp(
            jnp.where(lower[..., None], seg, 0.0)), 0.0)
        mix = (c @ b.T)[..., None] * decay * dt[None, :, :]     # (t, s, H)
        yh = jnp.einsum("tsh,shp->thp", mix, xh)
        yh = yh + xh * lw["D"].astype(f32)[None, :, None]
        g = yh.reshape(ln, di) * jax.nn.silu(z)
        g = _rms(g, lw["gate_norm"]["scale"].astype(f32), eps)
        return x + matmul(g, lw["wo"], mode), None

    with jax.default_matmul_precision("highest"):
        x, _ = jax.lax.scan(layer, x, w["layers"])
        x = _rms(x, w["final_norm"]["scale"].astype(f32), eps)
        return matmul(x, emb[:v].T, mode)


def token_gaps(w: Dict, m: Dict, tokens, mode: str):
    """As ``dense.token_gaps``."""
    ref = logits(w, m, tokens, "ref")
    if mode == "ref":
        nxt = jnp.concatenate([tokens[1:], tokens[-1:]])
    else:
        nxt = jnp.argmax(logits(w, m, tokens, mode), -1).astype(jnp.int32)
    return gaps(ref, nxt)


def flops_per_token(m: Dict, ctx: float) -> float:
    """2 per weight of every matmul (unembedding included) plus the SSD
    state update and read-out (2 x 2 H N P per token); independent of
    context."""
    nl, d, di, n, nh, p, w = _dims(m)
    per_layer = d * (2 * di + 2 * n + nh) + di * d
    return 2.0 * (nl * per_layer + d * m["vocab_size"]) \
        + 4.0 * nl * nh * n * p
