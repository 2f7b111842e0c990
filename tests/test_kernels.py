"""Pallas kernel tests: shape/dtype sweeps vs the pure-jnp oracles
(interpret=True executes the kernel body on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref
from repro.kernels.quantize_ef import quantize_ef
from repro.kernels.switch_blend import switch_blend
from repro.kernels.topk_block import block_topk


def _topk_fills(key, shape):
    """Blocks to select from: normal draws, then tie-heavy ones (many equal
    |x|, +-x pairs, all |x| equal) and all-zero ones holding +-0.0."""
    x = jax.random.normal(key, shape)
    signs = jnp.where(jax.random.bernoulli(key, 0.5, shape), 1.0, -1.0)
    zeros = jnp.where(signs > 0, 0.0, -0.0)
    return {"normal": x, "ties": jnp.round(x * 2) / 2, "pm": signs,
            "zeros": zeros}


@pytest.mark.parametrize("nblocks,block,k", [
    (1, 8, 2), (4, 64, 7), (2, 128, 16), (3, 256, 26), (2, 512, 51),
    # the smollm-360m wire's unaligned widths, 40 rows: one 32-row chunk
    # and a tail of 8
    (3, 960, 96), (2, 640, 64), (40, 320, 32), (2, 960, 1), (2, 320, 319)])
@pytest.mark.parametrize("dtype", [jnp.float32])
def test_topk_shapes(nblocks, block, k, dtype, key):
    for fill, x in _topk_fills(key, (nblocks, block)).items():
        x = x.astype(dtype)
        v, i = map(np.asarray, block_topk(x, k))
        vr, ir = map(np.asarray, ref.block_topk_ref(x, k))
        # the same offsets as lax.top_k, held in ascending offset, with
        # bitwise-equal values (ties to the lowest offset, -0.0 kept)
        order = np.argsort(ir, -1)
        np.testing.assert_array_equal(i[:, :k],
                                      np.take_along_axis(ir, order, -1),
                                      err_msg=fill)
        np.testing.assert_array_equal(
            v[:, :k].view(np.int32),
            np.take_along_axis(vr, order, -1).view(np.int32), err_msg=fill)
        # slot rows come padded to 128 lanes with zeros
        assert not v[:, k:].any() and not i[:, k:].any(), fill


def test_topk_bf16(key):
    x = jax.random.normal(key, (2, 128)).astype(jnp.bfloat16)
    v, _ = block_topk(x, 8)
    v = v[:, :8]
    vr, _ = ref.block_topk_ref(x, 8)
    np.testing.assert_allclose(
        np.sort(np.abs(np.asarray(v, np.float32)), -1),
        np.sort(np.abs(np.asarray(vr, np.float32)), -1), rtol=1e-2)


@pytest.mark.parametrize("nblocks,block,bits", [
    (1, 16, 4), (4, 128, 8), (2, 256, 5), (3, 64, 2)])
def test_quantize_ef_shapes(nblocks, block, bits, key):
    e = jax.random.normal(key, (nblocks, block))
    d = jax.random.normal(jax.random.fold_in(key, 1), (nblocks, block))
    v, en = quantize_ef(e, d, bits)
    vr, enr = ref.quantize_ef_ref(e, d, bits)
    np.testing.assert_allclose(np.asarray(v), np.asarray(vr), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(en), np.asarray(enr), rtol=1e-5, atol=1e-5)
    # EF identity: v + e_new == e + d exactly
    np.testing.assert_allclose(np.asarray(v + en), np.asarray(e + d),
                               rtol=1e-6, atol=1e-6)


@settings(max_examples=10, deadline=None)
@given(d=st.integers(3, 500), sigma=st.floats(0.0, 1.0),
       seed=st.integers(0, 2**16))
def test_switch_blend_property(d, sigma, seed):
    key = jax.random.PRNGKey(seed)
    gf = jax.random.normal(key, (d,))
    gg = jax.random.normal(jax.random.fold_in(key, 1), (d,))
    out = switch_blend(gf, gg, jnp.asarray(sigma), block=64)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.switch_blend_ref(gf, gg, sigma)),
                               rtol=1e-5, atol=1e-6)


def test_ops_topk_compress_matches_packing(key):
    from repro.configs.base import CompressorConfig
    from repro.core import packing
    # 1-D, block-divisible input => identical semantics for the flatten-based
    # Pallas wrapper and the last-axis packing path
    x = jax.random.normal(key, (2560,))
    cfg = CompressorConfig(kind="topk", ratio=0.2, block=128)
    via_kernel = ops.topk_compress(x, 0.2, block=128)
    via_packing = packing.block_topk_dense(x, cfg)
    np.testing.assert_allclose(np.asarray(via_kernel), np.asarray(via_packing),
                               rtol=1e-6, atol=1e-6)


def test_ops_quantize_tree_shapes(key):
    e = jax.random.normal(key, (7, 11))
    d = jax.random.normal(jax.random.fold_in(key, 1), (7, 11))
    v, en = ops.quantize_ef_apply(e, d, bits=6, block=32)
    assert v.shape == e.shape and en.shape == e.shape
    vr, enr = ref.quantize_ef_ref(
        jnp.pad((e + 0 * d).reshape(-1), (0, (-77) % 32)).reshape(-1, 32) * 0 + 0,
        jnp.zeros(((77 + 19) // 32, 32)), 6)  # shape check only
    np.testing.assert_allclose(np.asarray(v + en), np.asarray(e + d),
                               rtol=1e-6, atol=1e-6)


def test_pallas_transport_routes_through_kernels(key):
    """The comm layer's pallas backend emits the kernels' outputs: dense
    reconstruction equals the flatten-based ops.topk_compress wrapper on a
    1-D block-divisible input."""
    from repro import comm
    from repro.configs.base import CompressorConfig
    x = jax.random.normal(key, (2560,))
    cfg = CompressorConfig(kind="topk", ratio=0.2, block=128)
    t = comm.get_transport(cfg, "pallas")
    via_transport = t.decompress(t.compress({"w": x}), {"w": x})["w"]
    via_ops = ops.topk_compress(x, 0.2, block=128)
    np.testing.assert_allclose(np.asarray(via_transport), np.asarray(via_ops),
                               rtol=1e-6, atol=1e-6)


def test_switch_blend_tree(key):
    tree_f = {"a": jax.random.normal(key, (10,)),
              "b": jax.random.normal(key, (3, 4))}
    tree_g = jax.tree_util.tree_map(lambda x: -x, tree_f)
    out = ops.switch_blend_tree(tree_f, tree_g, jnp.asarray(0.5), block=8)
    for leaf in jax.tree_util.tree_leaves(out):
        np.testing.assert_allclose(np.asarray(leaf), 0.0, atol=1e-6)
