"""Jit'd high-level wrappers around the Pallas kernels: arbitrary-shape
arrays in, padded/blocked kernels underneath, pytree variants for FedSGM.

The aggregation entry points (:func:`scatter_agg`, :func:`quant_agg`,
:func:`segment_rows`) are *tuned*: each consults :mod:`repro.kernels.tune`
for a per-(shape, backend) implementation plan, so every aggregation call
site in the codebase -- ``FlatTransport.reduce``, the two-tier cohort
reduce, the tree ``_aggregate_packed``, the async StaleBuffer merge, and
the SlotStore restore -- lands on one implementation chosen once per shape.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import tune
from repro.kernels.quantize_ef import quantize_ef
from repro.kernels.switch_blend import switch_blend
from repro.kernels.topk_block import block_topk
from repro.kernels.unpack_mma import unpack_mma
from repro.obs import trace as obs_trace


def _to_blocks(x: jnp.ndarray, block: int):
    flat = x.reshape(-1)
    d = flat.shape[0]
    b = min(block, d)
    pad = (-d) % b
    return jnp.pad(flat, (0, pad)).reshape(-1, b), d


def topk_compress(x: jnp.ndarray, ratio: float, block: int = 1024,
                  interpret: bool | None = None) -> jnp.ndarray:
    """Dense block-topk compression of an arbitrary-shape array."""
    blocks, d = _to_blocks(x, block)
    nb, b = blocks.shape
    k = max(1, int(round(b * ratio)))
    if k >= b:
        return x
    with obs_trace.stage("kernel.block_topk"):
        vals, idx = block_topk(blocks, k, interpret=interpret)
    vals, idx = vals[:, :k], idx[:, :k]
    dense = jnp.zeros_like(blocks)
    dense = jax.vmap(lambda dst, i, v: dst.at[i].set(v))(dense, idx, vals)
    return dense.reshape(-1)[:d].reshape(x.shape)


def quantize_ef_apply(e: jnp.ndarray, delta: jnp.ndarray, bits: int,
                      block: int = 1024, interpret: bool | None = None):
    """Fused EF14 quantization for arbitrary-shape arrays."""
    eb, d = _to_blocks(e, block)
    db, _ = _to_blocks(delta, block)
    with obs_trace.stage("kernel.quantize_ef"):
        v, e_new = quantize_ef(eb, db, bits, interpret=interpret)
    unb = lambda t: t.reshape(-1)[:d].reshape(e.shape)
    return unb(v), unb(e_new)


# ---------------------------------------------------------------------------
# Tuned aggregation entry points (see repro.kernels.tune for plan selection)
# ---------------------------------------------------------------------------

def _scatter_agg_scatter(vals, idx, weight, block):
    n, nb, k = vals.shape
    pos = (jnp.arange(nb, dtype=jnp.int32) * block)[None, :, None] \
        + idx.astype(jnp.int32)
    wv = vals.astype(jnp.float32) * weight.astype(jnp.float32)[:, None, None]
    acc = jnp.zeros((nb * block,), jnp.float32)
    acc = acc.at[pos.reshape(-1)].add(wv.reshape(-1))
    return acc.reshape(nb, block)


def _scatter_agg_onehot(vals, idx, weight, block, chunk):
    """Chunked one-hot contraction: lax.map over tiles of ``chunk``
    destination blocks, each tile contracted as a dense per-block
    gather-multiply-accumulate (the CPU form of the Pallas bucket kernel --
    XLA serializes general scatter-add on CPU, this stays vectorized)."""
    n, nb, k = vals.shape
    chunk = max(1, min(chunk, nb))
    pad = (-nb) % chunk
    wv = vals.astype(jnp.float32) * weight.astype(jnp.float32)[:, None, None]
    ids = idx.astype(jnp.int32)
    if pad:
        wv = jnp.pad(wv, ((0, 0), (0, pad), (0, 0)))
        ids = jnp.pad(ids, ((0, 0), (0, pad), (0, 0)))
    nc = (nb + pad) // chunk
    wv = wv.reshape(n, nc, chunk, k).transpose(1, 0, 2, 3)
    ids = ids.reshape(n, nc, chunk, k).transpose(1, 0, 2, 3)
    iota = jnp.arange(block, dtype=jnp.int32)

    def tile(args):
        v, i = args                                     # [n, chunk, k]
        oh = (i[..., None] == iota).astype(jnp.float32)  # [n, chunk, k, block]
        return jnp.einsum("njk,njkb->jb", v, oh)

    out = jax.lax.map(tile, (wv, ids))                  # [nc, chunk, block]
    return out.reshape(-1, block)[:nb]


def _gemm_factor(block):
    """Split ``block`` into H * L lanes (H the power-of-two nearest
    sqrt(block)); falls back to 1 * block when block has no such split."""
    h = 1
    while h * h < block:
        h *= 2
    if block % h == 0:
        return h, block // h
    return 1, block


def _scatter_agg_gemm(vals, idx, weight, block, chunk):
    """Factored one-hot GEMM: the within-block offset splits as
    ``o = L * hi + lo``, so the bucket histogram is one batched matmul
    ``C[j, H, L] = (v * onehot(hi))^T @ onehot(lo)`` contracting the fused
    (client, slot) axis -- the 128-lane one-hot never materializes (only
    the H- and L-lane factors do, ~block/(H+L) times less memory traffic)
    and the contraction runs as a real GEMM instead of an elementwise
    reduce.  lax.map tiles ``chunk`` destination blocks at a time to bound
    the live one-hot factors."""
    n, nb, k = vals.shape
    chunk = max(1, min(chunk, nb))
    pad = (-nb) % chunk
    wv = vals.astype(jnp.float32) * weight.astype(jnp.float32)[:, None, None]
    ids = idx.astype(jnp.int32)
    if pad:
        wv = jnp.pad(wv, ((0, 0), (0, pad), (0, 0)))
        ids = jnp.pad(ids, ((0, 0), (0, pad), (0, 0)))
    nc = (nb + pad) // chunk
    # chunk-major item streams: [nc, chunk, n * k]
    wv = wv.reshape(n, nc, chunk, k).transpose(1, 2, 0, 3) \
        .reshape(nc, chunk, n * k)
    ids = ids.reshape(n, nc, chunk, k).transpose(1, 2, 0, 3) \
        .reshape(nc, chunk, n * k)
    H, L = _gemm_factor(block)

    def tile(args):
        v, i = args                                       # [chunk, n*k]
        ohh = (i[..., None] // L
               == jnp.arange(H, dtype=jnp.int32)).astype(jnp.float32)
        ohl = (i[..., None] % L
               == jnp.arange(L, dtype=jnp.int32)).astype(jnp.float32)
        A = (v[..., None] * ohh).transpose(0, 2, 1)       # [chunk, H, n*k]
        return jax.lax.batch_matmul(A, ohl)               # [chunk, H, L]

    out = jax.lax.map(tile, (wv, ids))                    # [nc, chunk, H, L]
    return out.reshape(-1, block)[:nb]


def scatter_agg(vals: jnp.ndarray, idx: jnp.ndarray, weight: jnp.ndarray,
                *, block: int, plan: tune.Plan | None = None,
                k: int | None = None,
                interpret: bool | None = None) -> jnp.ndarray:
    """Weighted bucket aggregation of stacked select payloads: vals
    [n, nblocks, slots] + within-block offsets idx [n, nblocks, slots] (in
    [0, block)) + weight [n] -> [nblocks, block] f32 with

        out[b, o] = sum_j sum_{t<k} weight[j] * vals[j,b,t] * 1[idx[j,b,t]==o].

    ``k`` (default: every slot) counts the real slots of a row; the flat
    wire buffers pad slot rows to 128 lanes.  Duplicate offsets within a
    block accumulate.  The implementation is the tuner's plan for this
    shape (``gemm`` factored one-hot batch-matmul on CPU, ``onehot``
    chunked contraction as the simpler alternative, the Pallas bucket
    kernel on TPU, native ``scatter`` on GPU)."""
    n, nb, slots = vals.shape
    k = slots if k is None else k
    if block == 1:
        return jnp.tensordot(weight.astype(jnp.float32),
                             vals[..., :k].astype(jnp.float32), axes=(0, 0))
    if plan is None:
        plan = tune.get_plan("scatter_agg", n=n, nblocks=nb, k=k, block=block)
    if plan.impl != "pallas":
        vals, idx = vals[..., :k], idx[..., :k]     # the kernel skips pads
    with obs_trace.stage(f"kernel.scatter_agg[{plan.impl}]"):
        if plan.impl == "gemm":
            return _scatter_agg_gemm(vals, idx, weight, block,
                                     int(plan.params.get("chunk", 8)))
        if plan.impl == "onehot":
            return _scatter_agg_onehot(vals, idx, weight, block,
                                       int(plan.params.get("chunk", 8)))
        if plan.impl == "pallas":
            from repro.kernels.scatter_agg import scatter_agg as kernel
            return kernel(vals, idx, weight, block,
                          rows=int(plan.params.get("rows", 8)), k=k,
                          interpret=interpret)
        return _scatter_agg_scatter(vals, idx, weight, block)


def quant_agg(words: jnp.ndarray, scale: jnp.ndarray, weight: jnp.ndarray,
              bits: int, block: int, plan: tune.Plan | None = None,
              interpret: bool | None = None) -> jnp.ndarray:
    """Weighted aggregation of stacked quant payloads: words [n, nblocks, W]
    + scale [n, nblocks] + weight [n] -> [nblocks, block] f32.  Plan impls:
    ``tensordot`` (unpack codes then contract; CPU default) or ``pallas``
    (the fused ``unpack_mma`` kernel; TPU default)."""
    n, nb, W = words.shape
    if plan is None:
        plan = tune.get_plan("quant_agg", n=n, nblocks=nb, W=W,
                             bits=bits, block=block)
    with obs_trace.stage(f"kernel.quant_agg[{plan.impl}]"):
        if plan.impl == "pallas":
            return unpack_mma(words, scale, weight.astype(jnp.float32),
                              bits, block, interpret=interpret)
        from repro.comm.payloads import unpack_codes
        levels = float(2 ** (bits - 1) - 1)
        codes = unpack_codes(words, bits, block)
        vals = codes.astype(jnp.float32) / levels * scale[..., None]
        return jnp.tensordot(weight.astype(jnp.float32), vals, axes=(0, 0))


def segment_rows(rows: jnp.ndarray, seg: jnp.ndarray, n: int,
                 plan: tune.Plan | None = None,
                 interpret: bool | None = None) -> jnp.ndarray:
    """Segment-sum of [m, ...] rows into [n, ...] population layout:
    ``out[i] = sum_{seg[j] == i} rows[j]`` (duplicate ids add).  Plan impls:
    ``xla`` scatter-add (CPU default) or the Pallas segment kernel (TPU)."""
    m = rows.shape[0]
    if plan is None:
        plan = tune.get_plan("segment_rows", m=m, n=n)
    with obs_trace.stage(f"kernel.segment_rows[{plan.impl}]"):
        if plan.impl == "pallas":
            from repro.kernels.scatter_agg import segment_rows as kernel
            out = kernel(rows.reshape(m, -1), seg, n,
                         crows=int(plan.params.get("crows", 8)),
                         cd=int(plan.params.get("cd", 512)),
                         interpret=interpret)
            return out.reshape((n,) + rows.shape[1:]).astype(rows.dtype)
        out = jnp.zeros((n,) + rows.shape[1:], rows.dtype)
        return out.at[seg].add(rows)


def switch_blend_tree(gf_tree, gg_tree, sigma, block: int = 4096,
                      interpret: bool | None = None):
    """Fused soft-switch blend over a gradient pytree."""
    return jax.tree_util.tree_map(
        lambda a, b: switch_blend(a.reshape(-1), b.reshape(-1), sigma,
                                  block=block, interpret=interpret
                                  ).reshape(a.shape),
        gf_tree, gg_tree)
