"""The SSD chunked scan (``repro.kernels.ssd_scan``): per row, head and
chunk of ``chunk`` tokens, the intra-chunk quadratic form and the carried
state, every layer.

``cost`` gives the work the chunked algorithm needs for one layer of one
prefill dispatch over the real tokens of each segment: per head and chunk
of r real tokens, 2 r^2 (N + P) FLOPs for C B^T and its product with
x dt, and 4 r N P for the read-out of the carried state and the state
update; bytes of reading x dt (bf16) and the cumulative decay (f32, as a
column and a row) per head and token, B and C (bf16) once per token,
writing y (bf16), and reading the initial and writing the final state
(f32) per row and head. Padding of short segments to the row length is
not work the algorithm needs."""

# the kernel in the device trace: a Pallas custom call inside a prefill
# executable (the configuration's only Pallas kernel on that path)
NAMES = ("tpu_custom_call",)
DISPATCH = "prefill"
# one call per layer and run: the count the trace must show
LAYERS = "n_layer"


def cost(seg_lens, heads: int, state: int, head_dim: int, chunk: int):
    """(flops, bytes) of one layer over segments of ``seg_lens`` tokens."""
    flops = nbytes = 0.0
    n, p = state, head_dim
    for ln in seg_lens:
        full, tail = divmod(int(ln), chunk)
        sq = full * chunk * chunk + tail * tail
        flops += heads * (2.0 * sq * (n + p) + 4.0 * ln * n * p)
        nbytes += ln * (heads * (2 * p + 8 + 2 * p) + 2 * 2 * n) \
            + heads * 2 * 4 * n * p
    return flops, nbytes
