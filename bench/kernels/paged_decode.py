"""Paged decode attention (``repro.kernels.paged_attention``): one query
token per row against the K/V pages of its context, every layer.

``cost`` gives the work the algorithm needs for one layer of one decode
dispatch, from the context length of each decoded row: FLOPs of QK and PV,
and bytes of reading each row's K and V once (bf16), its query and writing
its output. Rows the step masks out and pages past a row's length are not
work the algorithm needs."""

# the kernel in the device trace: a Pallas custom call inside the decode
# step's executable (its op name is only "closed_call.N")
NAMES = ("tpu_custom_call",)
DISPATCH = "decode"
# one call per layer and run: the count the trace must show
LAYERS = "num_hidden_layers"


def cost(ctx_lens, heads: int, kv_heads: int, head_dim: int,
         bytes_per: int = 2):
    """(flops, bytes) of one layer over rows attending ``ctx_lens``."""
    tokens = sum(ctx_lens)
    rows = len(ctx_lens)
    flops = 4.0 * heads * head_dim * tokens
    nbytes = bytes_per * (2.0 * kv_heads * head_dim * tokens
                          + 2.0 * heads * head_dim * rows)
    return flops, nbytes
