"""Compile every main-path Pallas kernel for a TPU v5e target at published
widths, with no chip attached.

Interpret mode runs a kernel's body in Python and cannot see what the
Mosaic lowering refuses (block shapes whose last two dims are neither whole
nor (8, 128) aligned, ops with no TPU lowering, VMEM over-use). Compiling
against a *described* ``v5e:2x2`` topology runs the real TPU compiler on
shapes alone, so those refusals fail here instead of on the chip.

Widths: qwen2-0.5b (H=14, KV=2, D=64), olmo-1b (H=KV=16, D=128) and the
mamba2-1.3b SSD (64 heads, P=64, N=128, chunk 128), all in bf16 as served.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library at a time, and under a
multi-worker pytest run every worker imports this file.
"""
from __future__ import annotations

import os

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
