"""``chip_smoke.py`` rehearsed on the CPU.

The script itself refuses anything but a TPU v5e. Its phases are plain
functions, so this test drives them at ``cfg.reduced()`` sizes with the
Pallas kernels in interpret mode: ``ops.default_backend`` is the one place
the layers ask which kernels to run, and the test points it at
``"interpret"``. That finds wrong paths, arguments and control flow before
any chip time is spent; it says nothing about speed.
"""
from __future__ import annotations

import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.configs import get_config
from repro.kernels import ops
from repro.utils import compile_cache

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def interpret_kernels(monkeypatch):
    monkeypatch.setattr(ops, "default_backend", lambda: "interpret")


def test_refuses_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                         cwd=ROOT, env=env, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode != 0
    assert "no TPU found" in out.stderr
    assert not any(line.startswith("{") for line in out.stdout.splitlines())


def test_kernels_match_oracles(smoke):
    errs = smoke.check_kernels([get_config("qwen2-0.5b").reduced()],
                               get_config("mamba2-1.3b").reduced(),
                               interpret=True)
    assert len(errs) == 6
    # fp32 activations are rounded to bf16 once; everything after is exact
    # enough to sit far inside the chip tolerance
    assert max(errs.values()) < smoke.KERNEL_TOL / 2


def test_tick_plane_serves_through_kernels(smoke, interpret_kernels):
    out = smoke.serve_tick_plane(get_config("qwen2-0.5b").reduced(),
                                 n_requests=4, prompt_lens=(100, 400),
                                 gen_tokens=4)
    assert out["tokens_out"] == 16
    assert out["max_packed_tokens"] >= 512 and out["chunk_prefills"] >= 1
    assert out["compiles_after_warmup"] == 0
    # fp32 twin: served and plain prefill differ only in reduction order
    assert out["logit_rel_err_max"] < 1e-4
    assert set(out["kernels"]) == {"decode", "packed_prefill",
                                   "chunk_prefill"}


def test_pool_serves_every_model(smoke, interpret_kernels):
    models = ["qwen2-0.5b", "mamba2-1.3b"]
    out = smoke.run_pool(models, reduced=True,
                         allocations={m: [1] for m in models})
    assert out["standby_engines"] == 2
    assert min(out["served"].values()) >= 1


def test_last_line_is_the_contract_json(smoke, monkeypatch, capsys):
    info = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    monkeypatch.setattr(smoke, "device_info", lambda: info)
    for phase in ("check_kernels", "run_pool"):
        monkeypatch.setattr(smoke, phase, lambda *a, **k: {})
    monkeypatch.setattr(smoke, "serve_tick_plane",
                        lambda *a, **k: {"kernels": {"decode": True}})
    monkeypatch.setattr("repro.utils.compile_cache.enable_compile_cache",
                        lambda: "cache")
    smoke.main()
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == {"ok": True, "device": info}


def test_compile_cache_location(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR wins untouched; without it the cache is
    the checkout's fixed, git-ignored ``.jax_cache``."""
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        fixed = str(ROOT / ".jax_cache")
        assert compile_cache.enable_compile_cache() == fixed
        assert jax.config.jax_compilation_cache_dir == fixed
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()
