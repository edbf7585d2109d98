"""The kernels' cost functions against counts made by hand."""
import pytest

from benchpath import BENCH


def _kernel(name):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        f"bench_kernel_{name}", BENCH / "kernels" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_paged_decode_cost_by_hand():
    k = _kernel("paged_decode")
    # 2 rows at contexts 3 and 5, 4 query heads over 2 KV heads of dim 8
    flops, nbytes = k.cost([3, 5], heads=4, kv_heads=2, head_dim=8)
    # QK and PV: 2 flops per multiply-add each, per head, dim and position
    assert flops == 4 * 4 * 8 * (3 + 5)
    # K and V of every position once (2 KV heads x 8 x 2 B each), plus
    # each row's query and output (4 heads x 8 x 2 B each)
    assert nbytes == 2 * (2 * 2 * 8 * 8) + 2 * (2 * 4 * 8 * 2)


def test_ssd_scan_cost_by_hand():
    k = _kernel("ssd_scan")
    # one segment of 5 tokens in chunks of 4: a full chunk and one of 1
    flops, nbytes = k.cost([5], heads=2, state=3, head_dim=2, chunk=4)
    sq = 4 * 4 + 1 * 1
    assert flops == 2 * (2 * sq * (3 + 2) + 4 * 5 * 3 * 2)
    per_token = 2 * (2 * 2 + 8 + 2 * 2) + 2 * 2 * 3
    assert nbytes == 5 * per_token + 2 * 2 * 4 * 3 * 2
    # padding a segment does not add work: cost is additive over segments
    f2, b2 = k.cost([5, 5], heads=2, state=3, head_dim=2, chunk=4)
    assert (f2, b2) == pytest.approx((2 * flops, 2 * nbytes))


@pytest.mark.parametrize("ref", ["dense", "ssm"])
def test_model_flops_grow_with_context_only_for_attention(ref):
    import json
    spec_name = {"dense": "olmo-1b", "ssm": "mamba2-1.3b"}[ref]
    conf = json.loads((BENCH / "configs" / f"{spec_name}.json").read_text())
    import importlib
    fam = importlib.import_module(f"models.{ref}")
    m = conf["model"]
    f1, f2 = fam.flops_per_token(m, 1), fam.flops_per_token(m, 1001)
    if ref == "dense":
        # 4 flops per (layer, head dim unit, position) for QK and PV
        assert f2 - f1 == pytest.approx(4 * 16 * 2048 * 1000)
        # 2 x (16 layers x (4 d^2 + 3 d ff) + d V), and one position
        layers = 16 * (4 * 2048 ** 2 + 3 * 2048 * 8192)
        assert f1 == 2 * (layers + 2048 * 50304) + 4 * 16 * 2048
    else:
        assert f2 == f1
        # 48 layers of in-projections (z, x, B, C, dt) and out-projection,
        # the unembedding, and 2 x 2 H N P per layer for the state
        layers = 48 * (2048 * (2 * 4096 + 2 * 128 + 64) + 4096 * 2048)
        assert f1 == 2 * (layers + 2048 * 50280) + 4 * 48 * 64 * 128 * 64
