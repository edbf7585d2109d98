"""Target-hardware constants (TPU v5e pod) used by the roofline latency
model, the knee analysis, and EXPERIMENTS.md §Roofline.

These are the constants mandated by the brief: 197 TFLOP/s bf16 per chip,
819 GB/s HBM, ~50 GB/s/link ICI. The dispatch overhead is the TPU analogue
of the paper's kernel-launch time t_np (XLA executable dispatch + ICI
collective launch)."""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Hardware:
    name: str = "tpu-v5e"
    # what jax reports as ``device_kind`` for this chip; a device not
    # listed here is not described by these constants
    device_kinds: tuple = ("TPU v5 lite", "TPU v5e")
    peak_flops: float = 197e12          # bf16 FLOP/s per chip
    hbm_bw: float = 819e9               # bytes/s per chip
    ici_bw: float = 50e9                # bytes/s per link
    ici_links: int = 4                  # 2D torus: 4 links per chip
    hbm_bytes: float = 16e9             # per chip
    chips_per_pod: int = 256
    dispatch_overhead: float = 6e-6     # s per fused layer step (t_np analogue)
    mxu_tile: int = 256                 # MXU-efficient per-dim tile
    # host-side contention when many engines multiplex one pod (paper §4.2
    # finds <3% with SM isolation; sub-mesh isolation behaves the same)
    multiplex_dilation: float = 0.02


V5E = Hardware()


# paper-comparison GPU (for the analytic-model benchmarks reproducing Fig. 2-4)
@dataclasses.dataclass(frozen=True)
class GPULike:
    name: str = "v100-like"
    n_units: int = 80                   # SMs
    t_p: float = 40.0                   # model units (paper Fig. 4 uses 40/10)
    t_np: float = 10.0


V100_LIKE = GPULike()
