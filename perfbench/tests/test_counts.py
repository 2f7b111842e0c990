"""The counting functions against hand counts at small shapes, and the
table of peaks."""
from __future__ import annotations

import pytest

from conftest import BENCH, ROOT

from harness import kernels, peaks, spec


def test_peaks_known_and_unknown():
    assert peaks.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("cpu")


def test_leaf_blocks_follow_the_wire():
    # 960 -> 960, 2560 -> 640 (largest divisor <= 1024), 320 -> 320
    got = kernels.leaf_blocks([(4, 960), (2, 960, 2560), (320,)], 1024)
    assert got == [(4, 960), (2 * 960 * 4, 640), (1, 320)]


def test_quantize_ef_pack_counts():
    # 2 rows x 3 blocks of 64 at 8 bits: 16 words a block
    ops, byts = kernels.quantize_ef_pack(3, 64, 8, rows=2)
    n = 2 * 3 * 64
    assert ops == 8 * n
    assert byts == 3 * 4 * n + 4 * 6 * 16 + 4 * 6


def test_unpack_mma_counts():
    ops, byts = kernels.unpack_mma(clients=4, nblocks=3, block=64, bits=4)
    # 8 codes a word at 4 bits: 8 words a block
    assert ops == 3 * 4 * 3 * 64
    assert byts == 4 * 4 * 3 * 8 + 4 * 4 * 3 + 4 * 3 * 64


def test_block_topk_and_scatter_agg_counts():
    ops, byts = kernels.block_topk(nblocks=5, block=100, k=10, rows=2)
    assert ops == 2 * 5 * 100
    assert byts == 4 * 2 * 5 * 100 + 2 * 5 * 10 * (4 + 2)
    ops, byts = kernels.scatter_agg(clients=3, nblocks=5, block=100, k=10)
    assert ops == 2 * 3 * 5 * 10
    assert byts == 3 * 5 * 10 * (4 + 2) + 4 * 5 * 100


def test_per_round_sums_leaves():
    shapes = [(8, 64), (128,)]
    up = {"kind": "topk", "ratio": 0.1, "block": 64}
    k = kernels.topk_k(64, 0.1)                 # 6
    assert k == 6
    ops, byts = kernels.per_round(
        shapes, up, lambda nb, b: kernels.block_topk(nb, b, k, 2))
    want = [kernels.block_topk(8, 64, k, 2), kernels.block_topk(2, 64, k, 2)]
    assert (ops, byts) == tuple(map(sum, zip(*want)))


@pytest.mark.parametrize("metric,uplink,gbytes", [
    ("quantize_ef_pack_roofline", {"kind": "quant", "bits": 8}, 9.411),
    ("unpack_mma_roofline", {"kind": "quant", "bits": 8}, 2.175),
    ("block_topk_roofline", {"kind": "topk", "ratio": 0.1}, 3.329),
    ("scatter_agg_roofline", {"kind": "topk", "ratio": 0.1}, 1.881)])
def test_readers_count_a_smollm_round(metric, uplink, gbytes):
    """A whole SmolLM-360M round of each kernel at n = m = 2: bytes by hand
    for quantize_ef_pack, 12 per element plus 240 words and a scale per
    block of 960 (the other three follow the same leaf geometry)."""
    import jax
    ref = spec.load_reference("smollm-360m")
    model = spec.load_cell(ROOT, "smollm-360m.quant8.e1").config["model"]
    shapes = [tuple(a.shape) for a in jax.tree_util.tree_leaves(
        jax.eval_shape(lambda: ref.init(jax.random.PRNGKey(0), model)))]
    mix = {"clients": 2, "participating": 2}
    counts = spec.load_module(
        f"{BENCH}/metrics/{metric}.py", metric).counts
    _, byts = kernels.per_round(
        shapes, uplink, lambda nb, b: counts(nb, b, uplink, mix))
    assert byts / 1e9 == pytest.approx(gbytes, abs=5e-4)
    if metric == "quantize_ef_pack_roofline":
        d = 361_821_120
        blocks = sum(nb for nb, _ in kernels.leaf_blocks(shapes, 1024))
        hand = 2 * (12 * d + 4 * blocks)
        hand += 2 * 4 * sum(nb * kernels.words_per_block(b, 8)
                            for nb, b in kernels.leaf_blocks(shapes, 1024))
        assert byts == hand


def test_model_flops_per_token_hand_counts():
    dense = spec.load_reference("smollm-360m")
    m = {"d_model": 8, "n_heads": 2, "n_kv_heads": 1, "d_ff": 16,
         "n_layers": 3, "vocab": 10}
    # per layer: q 8*8 + k,v 2*8*4 + o 8*8 + mlp 3*8*16 = 576;
    # attention 2 * 8 * (5 + 1) / 2 = 48; head 8 * 10
    fwd = 2 * (3 * (576 + 48) + 80)
    assert dense.flops_per_token(m, 5) == pytest.approx(3 * fwd)
    ssm = spec.load_reference("mamba2-130m")
    m = {"d_model": 8, "n_layers": 2, "vocab": 10,
         "ssm": {"expand": 2, "head_dim": 4, "d_state": 3, "n_groups": 1,
                 "d_conv": 4}}
    # d_inner 16, heads 4, conv_dim 16 + 6 = 22;
    # in_proj 8 * (32 + 6 + 4) + out_proj 16 * 8 = 464; conv 4 * 22 = 88;
    # state 3 * 4 * 4 * 3 = 144; head 80
    fwd = 2 * (2 * (464 + 88 + 144) + 80)
    assert ssm.flops_per_token(m, 512) == pytest.approx(3 * fwd)
