"""Shared helpers for the paper-figure benchmarks."""
from __future__ import annotations

import time
from typing import Dict, List

from repro.core.profiles import ModelProfile, build_profile
from repro.serving.request import Request, RequestGenerator

# the paper's 4-model mix (Alexnet/Mobilenet/ResNet-50/VGG-19 analog:
# two lightweight, one mid, one heavy)
C4 = ["qwen2-0.5b", "mamba2-1.3b", "deepseek-7b", "yi-9b"]
C7 = C4 + ["olmo-1b", "granite-moe-3b-a800m", "whisper-small"]


def profiles_for(names, rate=2000) -> Dict[str, ModelProfile]:
    return {n: build_profile(n, request_rate=rate) for n in names}


def generators_for(profiles, rate=2000, seed0=0):
    return [RequestGenerator(n, rate, profiles[n].slo, seed=seed0 + i)
            for i, n in enumerate(profiles)]


class Burst:
    """All requests at t=0 — fixed-work (Table 1) workloads."""

    def __init__(self, model: str, n: int, slo: float):
        self.reqs = [Request(0.0, i, model, slo) for i in range(n)]

    def until(self, t_end: float) -> List[Request]:
        out, self.reqs = self.reqs, []
        return out


def timed(fn, *args, **kw):
    t0 = time.perf_counter()
    out = fn(*args, **kw)
    return out, (time.perf_counter() - t0) * 1e6


# --------------------------------------------------------------------------
# shared machine-readable bench output (ISSUE 7): one schema for every
# benchmark's --json flag, so BENCH_*.json files form a comparable
# trajectory across PRs. CI validates each emitted file round-trips
# through validate_bench_json.
# --------------------------------------------------------------------------
BENCH_SCHEMA = "dstack-bench-v1"


def bench_payload(bench: str, rows, args=None, extra=None) -> dict:
    """Wrap a benchmark's ``(name, us_per_call, derived)`` rows in the
    shared schema. ``args`` records the CLI shape that produced the
    numbers (quick vs full runs are not comparable); ``extra`` carries
    bench-specific structured sections (scorecards, Prometheus
    snapshot, ...)."""
    return {
        "schema": BENCH_SCHEMA,
        "bench": str(bench),
        "args": dict(args or {}),
        "rows": [{"name": str(n), "us_per_call": float(us),
                  "derived": str(d)} for n, us, d in rows],
        "extra": dict(extra or {}),
    }


def write_json(path: str, payload: dict) -> dict:
    import json
    validate_bench_json(payload)
    with open(path, "w") as f:
        json.dump(payload, f, indent=1, sort_keys=True)
        f.write("\n")
    return payload


def validate_bench_json(payload) -> int:
    """Schema gate for the perf trajectory; returns the row count.
    Raises ``ValueError`` on the first violation."""
    if not isinstance(payload, dict):
        raise ValueError("bench json: not an object")
    if payload.get("schema") != BENCH_SCHEMA:
        raise ValueError(f"bench json: schema {payload.get('schema')!r} "
                         f"!= {BENCH_SCHEMA!r}")
    if not payload.get("bench"):
        raise ValueError("bench json: missing bench name")
    rows = payload.get("rows")
    if not isinstance(rows, list) or not rows:
        raise ValueError("bench json: rows missing or empty")
    for i, r in enumerate(rows):
        if not isinstance(r, dict) or not r.get("name"):
            raise ValueError(f"bench json: rows[{i}] malformed")
        us = r.get("us_per_call")
        if not isinstance(us, (int, float)) or us < 0:
            raise ValueError(f"bench json: rows[{i}].us_per_call {us!r}")
        if "derived" not in r:
            raise ValueError(f"bench json: rows[{i}] missing derived")
    for k in ("args", "extra"):
        if not isinstance(payload.get(k, {}), dict):
            raise ValueError(f"bench json: {k} is not an object")
    return len(rows)
