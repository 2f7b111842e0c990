"""Operations and HBM bytes the round's Pallas kernels need, from the
shapes of their calls, and a kernel's share of its roofline.

Counts are of the work the call needs, not of how a kernel does it: bytes
are each input read once and each output written once at its wire width
(the 128-lane padding of stored payload rows is not counted), and
operations are the elementwise ones.  A kernel's least time is the larger
of operations over the bf16 peak and bytes over the HBM bandwidth; its
roofline share is that least time over its measured device time, so it
cannot pass 100% unless the counts are too high or the time misses work.

Per-leaf geometry follows the wire: each leaf is cut into blocks along its
last axis, the block being the largest divisor of that axis not above the
compressor's block size.
"""
from __future__ import annotations

from harness.reference import choose_block

F32 = 4
U32 = 4
OFFSET = 2          # uint16 within-block offset of a top-k slot


def leaf_blocks(leaf_shapes, pref: int):
    """[(nblocks, block)] of each parameter leaf."""
    out = []
    for shape in leaf_shapes:
        d = shape[-1] if shape else 1
        size = 1
        for s in shape:
            size *= s
        b = choose_block(d, pref)
        out.append((size // b, b))
    return out


def words_per_block(block: int, bits: int) -> int:
    return -(-block // (32 // bits))


def topk_k(block: int, ratio: float) -> int:
    return max(1, min(block, int(round(block * ratio))))


def quantize_ef_pack(nblocks: int, block: int, bits: int, rows: int = 1):
    """(ops, bytes) of ``quantize_ef_pack`` on [rows, nblocks, block]:
    reads e and delta, writes the new residual, the packed words and one
    scale per block; 8 elementwise operations per element (add, abs, max,
    divide, round, multiply, subtract, pack)."""
    n = rows * nblocks * block
    nb = rows * nblocks
    return 8 * n, 3 * F32 * n + U32 * nb * words_per_block(block, bits) \
        + F32 * nb


def unpack_mma(clients: int, nblocks: int, block: int, bits: int):
    """(ops, bytes) of ``unpack_mma`` over ``clients`` stacked payloads:
    reads every client's words and scales, writes the f32 sum; an unpack
    and a multiply-add per element and client."""
    n = clients * nblocks * block
    return 3 * n, U32 * clients * nblocks * words_per_block(block, bits) \
        + F32 * clients * nblocks + F32 * nblocks * block


def block_topk(nblocks: int, block: int, k: int, rows: int = 1):
    """(ops, bytes) of ``block_topk`` on [rows, nblocks, block]: reads the
    blocks, writes k values and offsets per block; one comparison per
    element (a selection needs at least that)."""
    n = rows * nblocks * block
    return n, F32 * n + rows * nblocks * k * (F32 + OFFSET)


def scatter_agg(clients: int, nblocks: int, block: int, k: int):
    """(ops, bytes) of ``scatter_agg`` over ``clients`` stacked top-k
    payloads: reads every value and offset, writes the f32 sum; a
    multiply-add per slot and client."""
    slots = clients * nblocks * k
    return 2 * slots, slots * (F32 + OFFSET) + F32 * nblocks * block


def per_round(leaf_shapes, uplink: dict, per_leaf):
    """(ops, bytes) of one round's launches of a kernel over every
    parameter leaf, ``per_leaf(nblocks, block) -> (ops, bytes)`` giving a
    leaf's share."""
    ops = byts = 0
    for nb, b in leaf_blocks(leaf_shapes, uplink.get("block", 1024)):
        o, y = per_leaf(nb, b)
        ops += o
        byts += y
    return ops, byts


def roofline_share(ctx, kernel: str, kind: str, per_leaf):
    """100 * least time / device time of ``kernel`` over the traced window,
    or None where the cell's uplink is not ``kind`` or the trace holds no
    launch of the kernel.  ``per_leaf(nblocks, block, uplink, mix) ->
    (ops, bytes)`` counts one round's launches on one leaf; the encode
    kernels run once per participant, the aggregation kernels once over
    the stacked payloads of all clients."""
    up, mix = ctx.cell.traffic["uplink"], ctx.cell.traffic
    if up["kind"] != kind or not ctx.peaks:
        return None
    t = ctx.trace.kernel_s(kernel)
    if t <= 0:
        return None
    ops, byts = per_round(ctx.leaf_shapes, up,
                          lambda nb, b: per_leaf(nb, b, up, mix))
    least = max(ops / ctx.peaks["bf16_flops"],
                byts / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least * ctx.trace.rounds / t
