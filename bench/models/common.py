"""Shared pieces of the plain references: seeded weights made on the
device in one jitted call, and the matmul that the int8 control replaces.

Nothing here imports the program. The weight trees are laid out the way
the serving engine takes them (stacked layers), so the benchmark hands the
same arrays to the engine and, upcast, to its reference."""
from __future__ import annotations

from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp

Spec = Tuple[Tuple[int, ...], str, float]     # shape, init, std


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed, including ones wider than 32
    bits (the high word is folded in)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def make_weights(spec_tree: Dict, seed: int, dtype,
                 custom: Dict[str, Callable] = None) -> Dict:
    """Every leaf of ``spec_tree`` drawn in ``dtype`` on the default device
    in one jitted call. ``custom`` maps a leaf's path ("a/b/c") to a
    function (key, shape) -> float32 array for inits that are not
    normal/zeros/ones."""
    custom = custom or {}
    paths = []

    def collect(tree, prefix):
        for k in sorted(tree):
            v = tree[k]
            p = f"{prefix}/{k}" if prefix else k
            if isinstance(v, dict):
                collect(v, p)
            else:
                paths.append((p, v))
    collect(spec_tree, "")

    def build(key):
        keys = jax.random.split(key, max(1, len(paths)))
        flat = {}
        for k, (p, (shape, init, std)) in zip(keys, paths):
            if p in custom:
                flat[p] = custom[p](k, shape).astype(dtype)
            elif init == "zeros":
                flat[p] = jnp.zeros(shape, dtype)
            elif init == "ones":
                flat[p] = jnp.ones(shape, dtype)
            else:
                flat[p] = (std * jax.random.normal(k, shape, jnp.float32)
                           ).astype(dtype)
        return flat

    flat = jax.jit(build)(seed_key(seed))
    out: Dict[str, Any] = {}

    def place(tree, prefix, dst):
        for k in sorted(tree):
            v = tree[k]
            p = f"{prefix}/{k}" if prefix else k
            if isinstance(v, dict):
                dst[k] = {}
                place(v, p, dst[k])
            else:
                dst[k] = flat[p]
    place(spec_tree, "", out)
    return out


def _quant_int8(x, axis):
    """Symmetric int8 fake quantisation with one scale per slice along the
    contraction ``axis``: what an int8 kernel would read."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _quant_fp8(x, axis):
    """float8 (e4m3) fake quantisation, one scale per slice along
    ``axis`` mapping its largest magnitude to the format's largest."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def matmul(a, w, mode: str):
    """a (..., K) @ w (K, N) in float32. ``mode`` "ref" is the reference;
    "int8" and "fp8" are controls: activations per row and weights per
    output column rounded to that format first (W8A8)."""
    a = a.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if mode == "int8":
        a, w = _quant_int8(a, -1), _quant_int8(w, 0)
    elif mode == "fp8":
        a, w = _quant_fp8(a, -1), _quant_fp8(w, 0)
    return a @ w


def gaps(logits, tokens):
    """Per position: how far the logit of ``tokens[i]`` lies below the
    position's largest logit (0 where it is the largest)."""
    best = jnp.max(logits, axis=-1)
    got = jnp.take_along_axis(logits, tokens[:, None], axis=-1)[:, 0]
    return best - got
