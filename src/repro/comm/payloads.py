"""Wire payload formats for the transport layer (moved from core/packing.py).

A *payload* is the exact pytree a transport would put on the wire:

* :class:`PackedLeaf`  -- (values, indices) of block-wise top-k / rand-k,
* :class:`QuantPayload` -- (integer codes, per-block scale) of per-block
  max-abs symmetric b-bit rounding,
* a plain dense array (``none`` / ``natural``, paper-faithful simulation).

``comm="dense"`` decompresses before the cross-client collective, so XLA
moves full-model bytes.  ``comm="packed"`` moves only the payload across the
client axis and decompresses *after* the all-gather -- same math for
deterministic compressors, ~K/d wire bytes.

Blocking runs along the LAST tensor axis with a divisor-sized block
(no padding, leading dims untouched), so packing a sharded pytree leaf stays
a (mostly) shard-local operation -- flattening the whole leaf would force
GSPMD to all-gather it first, which dominated the memory/collective terms in
early dry-runs (EXPERIMENTS.md §Perf, refuted-hypothesis log).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import CompressorConfig


class PackedLeaf(NamedTuple):
    values: jnp.ndarray     # [..., nblocks, k]
    indices: jnp.ndarray    # [..., nblocks, k] uint16, index within block
                            # (block <= 65536 by construction: choose_block
                            # caps at the pref tile size)


class QuantPayload(NamedTuple):
    codes: jnp.ndarray      # [..., nblocks, block] int8 (int32 for bits > 8)
    scale: jnp.ndarray      # [..., nblocks, 1] float32 per-block max-abs


class FlatPacked(NamedTuple):
    """Block-select payload of a flat [d] buffer (comm.flat): the values and
    within-block offsets of every block of every leaf, concatenated in leaf
    order; in a large run each block's slot row is zero-padded to 128 lanes
    (pad slots hold value 0 at offset 0 and are not wire bytes).  Static block geometry
    lives in the :class:`repro.comm.flat.WireLayout`, not on the wire."""
    values: jnp.ndarray     # [..., K_total] buffer dtype
    indices: jnp.ndarray    # [..., K_total] uint16 within-block offsets


class FlatQuant(NamedTuple):
    """Bit-packed quantization payload of a flat [d] buffer: b-bit biased
    codes packed ``32 // b`` to a uint32 word (the true wire format -- HBM
    and collective traffic shrink 8/b x vs int8 words), plus one fp32
    max-abs scale per block.  In a large run each block's word row is
    zero-padded to 128 lanes; the pad words are not wire bytes."""
    words: jnp.ndarray      # [..., W_total] uint32
    scale: jnp.ndarray      # [..., nblocks_total] float32


INDEX_DTYPE = jnp.uint16    # PackedLeaf/FlatPacked within-block offsets


def is_payload(x) -> bool:
    return isinstance(x, (PackedLeaf, QuantPayload, FlatPacked, FlatQuant))


def choose_block(D: int, pref: int, shards: int = 1) -> int:
    """Largest divisor of D (and, when possible, of the per-shard chunk
    D/shards) that is <= pref -- exact blocking, no padding, shard-local."""
    base = D // shards if shards > 1 and D % shards == 0 else D
    b = max(1, min(pref, base))
    while base % b:
        b -= 1
    return b


_SORT_FREE_MIN = 1 << 22   # leaves above this use threshold selection


def _block_threshold(absx: jnp.ndarray, k: int, iters: int = 25):
    """Binary-search the k-th largest |x| per block (sort-free top-k).

    XLA SPMD replicates sort operands wholesale, which made lax.top_k on
    model-scale EF buffers all-gather hundreds of GB (EXPERIMENTS.md §Perf
    A0); 25 rounds of elementwise compare + block-local count partition
    perfectly.  Returns thr with count(|x| > thr) in [~k, k + ties]."""
    hi = jnp.max(absx, axis=-1, keepdims=True)
    lo = jnp.zeros_like(hi)

    def body(_, carry):
        lo, hi = carry
        mid = 0.5 * (lo + hi)
        cnt = jnp.sum(absx > mid, axis=-1, keepdims=True)
        too_many = cnt > k
        return jnp.where(too_many, mid, lo), jnp.where(too_many, hi, mid)

    lo, hi = jax.lax.fori_loop(0, iters, body, (lo, hi))
    return lo


def block_geometry(D: int, cfg: CompressorConfig) -> tuple[int, int]:
    """(block, k) for block-wise top-k/rand-k along a last axis of size D."""
    b = choose_block(D, cfg.block, cfg.shards)
    k = max(1, min(b, int(round(b * cfg.ratio))))
    return b, k


def select_topk_blocks(blocks: jnp.ndarray, k: int, sort_free: bool):
    """Per-block magnitude top-k of a [..., nblocks, block] view -- the ONE
    copy of the selection math shared by the tree packed path
    (:func:`block_topk_pack`) and the flat hot path (comm.flat), so their
    payloads can never drift.  ``sort_free`` selects the threshold +
    cumsum-slotting regime used for mesh-scale leaves (see
    :func:`_block_threshold`); returns (values, uint16 offsets)."""
    b = blocks.shape[-1]
    if k >= b:
        idx = jnp.broadcast_to(
            jnp.arange(b, dtype=INDEX_DTYPE), blocks.shape).copy()
        return blocks, idx
    if not sort_free:
        _, idx = jax.lax.top_k(jnp.abs(blocks), k)
        vals = jnp.take_along_axis(blocks, idx, axis=-1)
        return vals, idx.astype(INDEX_DTYPE)
    absx = jnp.abs(blocks)
    thr = _block_threshold(absx, k)
    keep = absx > thr
    pos = jnp.cumsum(keep.astype(jnp.int32), axis=-1) - 1
    slot = jnp.where(keep & (pos < k), pos, k)          # overflow -> slot k
    vals = jnp.zeros(blocks.shape[:-1] + (k + 1,), blocks.dtype)
    vals = jnp.put_along_axis(vals, slot, blocks * keep, axis=-1,
                              inplace=False)[..., :k]
    iota = jnp.broadcast_to(
        jnp.arange(b, dtype=jnp.int32), blocks.shape)
    idx = jnp.zeros(blocks.shape[:-1] + (k + 1,), jnp.int32)
    idx = jnp.put_along_axis(idx, slot, iota, axis=-1,
                             inplace=False)[..., :k]
    return vals, idx.astype(INDEX_DTYPE)


def block_topk_pack(x: jnp.ndarray, cfg: CompressorConfig) -> PackedLeaf:
    """Block-wise magnitude top-k along the last axis.

    Small leaves use exact lax.top_k; mesh-scale leaves use the sort-free
    threshold + cumsum-slotting path (see :func:`_block_threshold`)."""
    if x.ndim == 0:
        x = x.reshape(1)
    D = x.shape[-1]
    b, k = block_geometry(D, cfg)
    blocks = x.reshape(x.shape[:-1] + (D // b, b))
    return PackedLeaf(*select_topk_blocks(blocks, k,
                                          x.size > _SORT_FREE_MIN))


def block_randk_pack(x: jnp.ndarray, cfg: CompressorConfig,
                     key: jax.Array) -> PackedLeaf:
    """Block-wise rand-k: k uniformly random coordinates per block (no
    rescale), same (values, indices) wire format as top-k."""
    if x.ndim == 0:
        x = x.reshape(1)
    D = x.shape[-1]
    b, k = block_geometry(D, cfg)
    blocks = x.reshape(x.shape[:-1] + (D // b, b))
    if k >= b:
        idx = jnp.broadcast_to(
            jnp.arange(b, dtype=INDEX_DTYPE), blocks.shape).copy()
        return PackedLeaf(blocks, idx)
    # distinct indices per block: argsort of iid uniforms = random permutation
    u = jax.random.uniform(key, blocks.shape)
    idx = jnp.argsort(u, axis=-1)[..., :k]
    vals = jnp.take_along_axis(blocks, idx, axis=-1)
    return PackedLeaf(vals, idx.astype(INDEX_DTYPE))


def block_topk_unpack(p: PackedLeaf, shape, dtype=jnp.float32,
                      block: int | None = None) -> jnp.ndarray:
    """Inverse of :func:`block_topk_pack` (dense with zeros elsewhere)."""
    if len(shape) == 0:
        return block_topk_unpack(p, (1,), dtype, block).reshape(())
    D = shape[-1]
    nb = p.values.shape[-2]
    b = D // nb if block is None else block
    dense = jnp.zeros(tuple(shape[:-1]) + (nb, b), dtype=p.values.dtype)
    dense = jnp.put_along_axis(dense, p.indices.astype(jnp.int32), p.values,
                               axis=-1, inplace=False)
    return dense.reshape(shape).astype(dtype)


def block_topk_dense(x: jnp.ndarray, cfg: CompressorConfig) -> jnp.ndarray:
    """Dense result of blockwise top-k (pack -> unpack); contraction q~k/b."""
    if x.ndim == 0:
        return x
    D = x.shape[-1]
    b, k = block_geometry(D, cfg)
    if x.size > _SORT_FREE_MIN and b > 1:
        # sort-free fast path: mask below the per-block k-th-largest threshold
        blocks = x.reshape(x.shape[:-1] + (D // b, b))
        if k >= b:
            return x
        absx = jnp.abs(blocks)
        keep = absx > _block_threshold(absx, k)
        return (blocks * keep).reshape(x.shape)
    return block_topk_unpack(block_topk_pack(x, cfg), x.shape, x.dtype, block=b)


# ---------------------------------------------------------------------------
# Quantization payload (per-block max-abs symmetric b-bit rounding)
# ---------------------------------------------------------------------------

def quant_code_dtype(bits: int):
    return jnp.int8 if bits <= 8 else jnp.int32


def quant_blocks(blocks: jnp.ndarray, bits: int):
    """Per-block max-abs symmetric b-bit rounding of a [..., nblocks,
    block] view -- the ONE copy of the quantizer math shared by the tree
    packed path (:func:`quant_pack`) and the flat hot path (comm.flat).
    Returns (float codes in [-L, L], scale with keepdims)."""
    scale = jnp.max(jnp.abs(blocks), axis=-1, keepdims=True)
    levels = float(2 ** (bits - 1) - 1)
    safe = jnp.where(scale > 0, scale, 1.0)
    return jnp.round(blocks / safe * levels), scale


def quant_pack(x: jnp.ndarray, cfg: CompressorConfig) -> QuantPayload:
    """Integer codes + per-block scale; round-trips bit-for-bit with the
    dense quantizer (codes are small exact integers)."""
    if x.ndim == 0:
        x = x.reshape(1)
    D = x.shape[-1]
    b = choose_block(D, cfg.block, cfg.shards)
    blocks = x.reshape(x.shape[:-1] + (D // b, b))
    codes, scale = quant_blocks(blocks, cfg.bits)
    return QuantPayload(codes.astype(quant_code_dtype(cfg.bits)),
                        scale.astype(jnp.float32))


def quant_unpack(p: QuantPayload, shape, dtype, cfg: CompressorConfig) -> jnp.ndarray:
    if len(shape) == 0:
        return quant_unpack(p, (1,), dtype, cfg).reshape(())
    levels = float(2 ** (cfg.bits - 1) - 1)
    vals = p.codes.astype(jnp.float32) / levels * p.scale
    vals = jnp.where(p.scale > 0, vals, 0.0)
    return vals.reshape(shape).astype(dtype)


# ---------------------------------------------------------------------------
# Bit-packed wire words (the flat hot path's quant format, comm.flat)
# ---------------------------------------------------------------------------
#
# b-bit symmetric codes in [-L, L] (L = 2^(b-1) - 1) ship as BIASED unsigned
# lanes (code + L in [0, 2L]) packed 32//b to a uint32 word, little-endian in
# the lane index.  ``bits`` must divide 32 (2/4/8 are the supported wire
# widths); blocks whose size is not a multiple of 32//b pad the trailing word
# with zero lanes -- unpack trims them, so the round-trip is exact for any
# block size.

PACK_BITS = (2, 4, 8)


def words_per_block(block: int, bits: int) -> int:
    """uint32 words needed for one ``block``-code payload at ``bits`` wide."""
    per_word = 32 // bits
    return -(-block // per_word)


def pack_codes(codes: jnp.ndarray, bits: int) -> jnp.ndarray:
    """[..., block] integer codes in [-L, L] -> [..., W] uint32 words."""
    if bits not in PACK_BITS:
        raise ValueError(f"bits={bits} not packable; expected {PACK_BITS}")
    per_word = 32 // bits
    block = codes.shape[-1]
    W = words_per_block(block, bits)
    levels = 2 ** (bits - 1) - 1
    biased = (codes.astype(jnp.int32) + levels).astype(jnp.uint32)
    pad = W * per_word - block
    if pad:
        biased = jnp.pad(biased, [(0, 0)] * (biased.ndim - 1) + [(0, pad)])
    lanes = biased.reshape(biased.shape[:-1] + (W, per_word))
    shifts = jnp.arange(per_word, dtype=jnp.uint32) * jnp.uint32(bits)
    # lanes fit disjoint bit ranges, so the OR-accumulate is a plain sum
    return jnp.sum(lanes << shifts, axis=-1, dtype=jnp.uint32)


def unpack_codes(words: jnp.ndarray, bits: int, block: int) -> jnp.ndarray:
    """[..., W] uint32 words -> [..., block] int32 codes (exact inverse).

    Bitcasts each word to its 4 little-endian bytes first, so only
    ``8 // bits`` shift/mask lanes run per byte instead of ``32 // bits``
    per word (bits=8 unpacks with no shifts at all) -- the unpack is on the
    aggregation hot path for every buffered payload."""
    levels = 2 ** (bits - 1) - 1
    by = jax.lax.bitcast_convert_type(words, jnp.uint8)
    by = by.reshape(words.shape[:-1] + (-1,))          # [..., W * 4]
    if bits == 8:
        flat = by
    else:
        per_byte = 8 // bits
        mask = jnp.uint8((1 << bits) - 1)
        lanes = [(by >> jnp.uint8(bits * i)) & mask for i in range(per_byte)]
        flat = jnp.stack(lanes, axis=-1).reshape(by.shape[:-1] + (-1,))
    return flat[..., :block].astype(jnp.int32) - levels


# ---------------------------------------------------------------------------
# Tree-level helpers and byte accounting
# ---------------------------------------------------------------------------

def pack_tree(tree, cfg: CompressorConfig):
    return jax.tree_util.tree_map(lambda l: block_topk_pack(l, cfg), tree)


def unpack_tree(packed, like_tree, cfg: CompressorConfig | None = None):
    def one(p, ref):
        block = (choose_block(ref.shape[-1] if ref.ndim else 1,
                              cfg.block, cfg.shards)
                 if cfg is not None else None)
        return block_topk_unpack(p, ref.shape, ref.dtype, block=block)
    return jax.tree_util.tree_map(
        one, packed, like_tree,
        is_leaf=lambda n: isinstance(n, PackedLeaf),
    )


def packed_bytes(packed) -> int:
    """Materialized bytes of a payload pytree (sum of leaf array bytes)."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(packed):
        total += leaf.size * leaf.dtype.itemsize
    return int(total)


def payload_wire_bytes(payload, bits: int | None = None) -> int:
    """Logical wire bytes of a payload pytree.

    Identical to :func:`packed_bytes` except quantizer codes count at their
    logical width (``bits``/8 bytes each -- the simulation materializes int8,
    the wire format packs sub-byte codes)."""
    total = 0.0

    def visit(node):
        nonlocal total
        if isinstance(node, QuantPayload):
            total += node.codes.size * (bits or 8 * node.codes.dtype.itemsize) / 8
            total += node.scale.size * 4
        else:
            for leaf in jax.tree_util.tree_leaves(node):
                total += leaf.size * leaf.dtype.itemsize

    jax.tree_util.tree_map(visit, payload, is_leaf=is_payload)
    return int(total)
