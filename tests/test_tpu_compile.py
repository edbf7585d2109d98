"""Compile every main-path Pallas kernel for a TPU v5e target at published
widths, with no chip attached.

Interpret mode runs a kernel's body in Python and cannot see what the
Mosaic lowering refuses (block shapes whose last two dims are neither whole
nor (8, 128) aligned, ops with no TPU lowering, VMEM over-use). Compiling
against a *described* ``v5e:2x2`` topology runs the real TPU compiler on
shapes alone, so those refusals fail here instead of on the chip.

Widths: qwen2-0.5b (H=14, KV=2, D=64), olmo-1b (H=KV=16, D=128) and the
mamba2-1.3b SSD (64 heads, P=64, N=128, chunk 128), all in bf16 as served.

The serving steps over the paged K/V pool are compiled whole as well, to
count what the compiler does around the kernels: no instruction of the
decode step, the segment write or the chunk prefill may yield a buffer of
the pool's shape, or of one layer's, other than by passing it through.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library at a time, and under a
multi-worker pytest run every worker imports this file.
"""
from __future__ import annotations

import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.chunk_attention import paged_chunk_attention
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import (flash_attention,
                                           segment_flash_attention)
from repro.kernels.paged_attention import paged_decode_attention
from repro.kernels.ssd_scan import ssd_scan
from repro.models.registry import build_model
from repro.serving import engine

BF16 = jnp.bfloat16
I32 = jnp.int32

ATTN_MODELS = ["olmo-1b", "qwen2-0.5b"]
PAGE_SIZE = 16          # the tick plane's pages; the pool's are 8


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep it out of the cache
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cached)


def _compile_text(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _assert_kernel(text):
    assert "tpu_custom_call" in text


def _widths(model):
    cfg = get_config(model)
    return cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim


@pytest.mark.parametrize("page_size", [8, PAGE_SIZE])
@pytest.mark.parametrize("model", ATTN_MODELS)
def test_paged_decode_compiles(one_chip, model, page_size):
    h, kv, d = _widths(model)
    b, max_pages = 8, 64
    pages = b * max_pages + 1
    _assert_kernel(_compile_text(
        paged_decode_attention, one_chip,
        ((b, h, d), BF16), ((kv, pages, page_size, d), BF16),
        ((kv, pages, page_size, d), BF16), ((b, max_pages), I32),
        ((b,), I32)))


@pytest.mark.parametrize("model", ATTN_MODELS)
def test_ragged_decode_compiles(one_chip, model):
    h, kv, d = _widths(model)
    b, c = 8, 1024
    _assert_kernel(_compile_text(
        decode_attention, one_chip,
        ((b, h, d), BF16), ((b, c, kv, d), BF16), ((b, c, kv, d), BF16),
        ((b,), I32)))


@pytest.mark.parametrize("model", ATTN_MODELS)
def test_chunk_attention_compiles(one_chip, model):
    h, kv, d = _widths(model)
    s, r, max_pages = 4, 64, 32
    pages = s * max_pages + 1
    _assert_kernel(_compile_text(
        paged_chunk_attention, one_chip,
        ((s, r, h, d), BF16), ((kv, pages, PAGE_SIZE, d), BF16),
        ((kv, pages, PAGE_SIZE, d), BF16), ((s, r, kv, d), BF16),
        ((s, r, kv, d), BF16), ((s, max_pages), I32), ((s,), I32),
        ((s,), I32)))


@pytest.mark.parametrize("model", ATTN_MODELS)
def test_flash_attention_compiles(one_chip, model):
    h, kv, d = _widths(model)
    s = 1024
    _assert_kernel(_compile_text(
        flash_attention, one_chip,
        ((1, s, h, d), BF16), ((1, s, kv, d), BF16), ((1, s, kv, d), BF16)))


# a packed row of 512-multiples takes 512 tiles, the half-step buckets
# (3 * 2^k) take 256 tiles (models/layers.py packed_prefill_attention)
@pytest.mark.parametrize("model,t,blk", [
    ("qwen2-0.5b", 1024, 512), ("qwen2-0.5b", 768, 256),
    ("olmo-1b", 1024, 512)])
def test_segment_flash_compiles(one_chip, model, t, blk):
    h, kv, d = _widths(model)

    def fn(q, k, v, seg):
        return segment_flash_attention(q, k, v, seg, block_q=blk,
                                       block_k=blk)

    _assert_kernel(_compile_text(
        fn, one_chip, ((1, t, h, d), BF16), ((1, t, kv, d), BF16),
        ((1, t, kv, d), BF16), ((t,), I32)))


@pytest.mark.parametrize("batch,n_chunks", [(1, 8), (2, 1)])
def test_ssd_scan_compiles(one_chip, batch, n_chunks):
    cfg = get_config("mamba2-1.3b")
    heads, p, n, cl = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state,
                       cfg.ssm_chunk)
    f32 = jnp.float32
    _assert_kernel(_compile_text(
        ssd_scan, one_chip,
        ((batch * heads, n_chunks, cl, p), f32),
        ((batch * heads, n_chunks, cl), f32),
        ((batch, n_chunks, cl, n), f32), ((batch, n_chunks, cl, n), f32),
        ((batch * heads, n, p), f32)))


# instructions that pass a buffer through without making one
_PASS_THROUGH = ("parameter", "get-tuple-element", "tuple", "bitcast")


def _made_buffers(text, layer):
    """Instructions of an optimized HLO module, outside the bodies of its
    fusions, that make a bf16 buffer of one or more layers of a pool whose
    layer is ``layer`` (its shape ends in ``layer``'s dims) and do not
    just pass one through."""
    fused = set(re.findall(r"calls=%([\w.\-]+)", text))
    comp, made = None, []
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%([\w.\-]+) ", line)
        if head and line.rstrip().endswith("{"):
            comp = head.group(1)
            continue
        inst = re.match(r"^\s+(?:ROOT )?%([\w.\-]+) = bf16\[([\d,]*)\]\S* "
                        r"([\w\-]+)\(", line)
        if not inst or comp in fused or inst.group(3) in _PASS_THROUGH:
            continue
        dims = tuple(int(x) for x in inst.group(2).split(",") if x)
        if dims[-len(layer):] == tuple(layer):
            made.append(f"{comp}: {inst.group(1)} {inst.group(3)}")
    return made


def test_paged_steps_never_copy_the_pool(one_chip, monkeypatch):
    """The engine's decode step (donating the cache, as the engine does),
    its segment write and the chunk prefill over olmo-1b's paged pool: the
    pool keeps one layout, written by row scatters in place and read by
    the kernels at a layer index, so nothing pool-shaped is made; the
    decode step holds one kernel call (once per layer, in its loop).

    The pool is the olmo-1b cell's (256 pages of 128 tokens and the null
    page) and two layers: a pool small enough for the chip's on-core
    memory is moved there whole, which would be counted here but is not
    what the serving size compiles to."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "default_backend", lambda: "pallas")
    cfg = dataclasses.replace(get_config("olmo-1b"), num_layers=2)
    api = build_model(cfg)
    n_slots, page_size, max_pages, pages = 64, 128, 16, 257
    t, segs = 256, 2

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    def arg(shape, dtype=I32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = on_chip(jax.eval_shape(
        lambda: api.init(jax.random.PRNGKey(0), BF16)))
    cache = on_chip(jax.eval_shape(
        lambda: api.init_paged_cache(n_slots, pages, page_size, max_pages)))
    kv, d = cfg.num_kv_heads, cfg.resolved_head_dim
    layer = (kv, pages, page_size, d)
    assert cache["k"].shape == (cfg.num_layers,) + layer

    skip = frozenset(api.paged_keys) | {"block_tables"}

    def dstack_slot_step(p, tok, c, active):
        return engine._slot_decode_step(api, skip, p, tok, c, active)

    decode = jax.jit(dstack_slot_step, donate_argnums=(2,)).lower(
        params, arg((n_slots,)), cache, arg((n_slots,), jnp.bool_))
    decode = decode.compile().as_text()

    packed = {"k": arg((cfg.num_layers, t, kv, d), BF16),
              "v": arg((cfg.num_layers, t, kv, d), BF16),
              "pos": arg((segs,))}
    write = jax.jit(engine._make_write_segments(api.paged_keys),
                    donate_argnums=(0, 1)).lower(
        cache, arg((n_slots,)), packed,
        arg((segs, cfg.vocab_size), jnp.float32), arg((t,)), arg((t,)),
        arg((segs,)), arg((segs, max_pages)))
    write = write.compile().as_text()

    chunk_in = {"tokens": arg((1, t)), "seg_ids": arg((t,)),
                "seg_starts": arg((segs,)), "seg_lens": arg((segs,)),
                "seg_slots": arg((segs,)), "hist_lens": arg((segs,))}

    def dstack_prefill_chunk(p, pk, c):
        return api.prefill_chunk(p, pk, c, t)

    chunk = jax.jit(dstack_prefill_chunk).lower(params, chunk_in, cache)
    chunk = chunk.compile().as_text()

    for name, text in (("decode", decode), ("segment write", write),
                       ("chunk prefill", chunk)):
        assert _made_buffers(text, layer) == [], name
    assert decode.count("tpu_custom_call") == 1
    assert chunk.count("tpu_custom_call") == 1
