"""Pallas TPU kernels: bucketed select-payload aggregation + segment-sum.

``scatter_agg`` is the accelerator form of the select-payload reduction

    acc[b, o] = sum_j sum_t  weight_j * vals[j, b, t] * 1[idx[j, b, t] == o]

over stacked client payloads (FlatPacked values + within-block offsets).
Because select positions are ``block_base + within_block_offset``, the [n]
client streams aimed at one destination block form a *bucket*: the kernel
contracts each bucket as a dense one-hot gather-multiply-accumulate instead
of a serialized general scatter -- destination blocks ride the outer grid
dimension (``rows`` blocks per program, the autotuner's rows-per-program
knob) and the client axis rides the inner grid dimension, so each output
tile is revisited consecutively (TPU output-revisit rule) and accumulates
in VMEM.  No atomics, no data-dependent control flow: the one-hot compare
vectorizes on the VPU, one payload slot at a time over a sublane-aligned
tile of destination blocks.  Client weights and segment ids sit in SMEM
(:mod:`repro.kernels.tiling`).

``segment_rows`` is the companion segment-sum covering the ``scatter_rows``
expansion ([m, D] participant rows -> [n, D] population layout): each grid
step holds all m rows of one feature chunk and one population chunk of the
output, and adds row ``j`` where the output row id equals ``seg_j``.
Duplicate segment ids *add* (true segment-sum semantics); the engine's
unique-id scatter is the special case where add == set.

Both kernels run in interpret mode off-TPU; the CPU hot path uses the
tuned jnp formulations in :mod:`repro.kernels.ops` instead (see
:mod:`repro.kernels.tune`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import tiling


def _agg_kernel(weight_ref, vals_ref, idx_ref, acc_ref, *, k: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros(acc_ref.shape, acc_ref.dtype)

    v = vals_ref[...].astype(jnp.float32) * weight_ref[j]   # [rows, slots]
    ids = idx_ref[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, acc_ref.shape, 1)
    acc = acc_ref[...]
    for t in range(k):
        acc += jnp.where(lane == ids[:, t:t + 1], v[:, t:t + 1], 0.0)
    acc_ref[...] = acc


@functools.partial(jax.jit,
                   static_argnames=("block", "rows", "k", "interpret"))
def scatter_agg(vals: jnp.ndarray, idx: jnp.ndarray, weight: jnp.ndarray,
                block: int, rows: int = 8, k: int | None = None,
                interpret: bool | None = None) -> jnp.ndarray:
    """vals [n, nblocks, slots] + idx [n, nblocks, slots] (within-block
    offsets in [0, block)) + weight [n] -> weighted bucket sums
    [nblocks, block] f32 over the first ``k`` slots of each block (all of
    them by default; the flat wire buffers pad their slot rows to 128).

    ``rows`` is the destination-blocks-per-program tile (the autotuner's
    rows-per-program knob), rounded up to a multiple of 8 sublanes; a
    partial last tile reads undefined slots for blocks past ``nblocks``
    and its writes there are dropped."""
    n, nblocks, slots = vals.shape
    k = slots if k is None else k
    tr = tiling.row_tile(nblocks, block,
                         cap=tiling.round_up(rows, tiling.SUBLANE))
    kp = tiling.lanes(slots)
    return pl.pallas_call(
        functools.partial(_agg_kernel, k=k),
        grid=(pl.cdiv(nblocks, tr), n),
        in_specs=[tiling.smem_spec(),
                  pl.BlockSpec((None, tr, kp), lambda i, j: (j, i, 0)),
                  pl.BlockSpec((None, tr, kp), lambda i, j: (j, i, 0))],
        out_specs=pl.BlockSpec((tr, tiling.lanes(block)), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nblocks, block), jnp.float32),
        name="scatter_agg",
        interpret=tiling.interpret_mode(interpret),
    )(weight.astype(jnp.float32), vals, idx.astype(jnp.int32))


def _seg_kernel(seg_ref, rows_ref, acc_ref, *, m: int):
    crows = acc_ref.shape[0]
    rid = jax.lax.broadcasted_iota(jnp.int32, (crows, 1), 0) \
        + pl.program_id(0) * crows

    def add(j, acc):
        row = rows_ref[pl.ds(j, 1), :].astype(jnp.float32)   # [1, cd]
        return acc + jnp.where(rid == seg_ref[j], row, 0.0)

    acc_ref[...] = jax.lax.fori_loop(
        0, m, add, jnp.zeros(acc_ref.shape, jnp.float32))


@functools.partial(jax.jit,
                   static_argnames=("n", "crows", "cd", "interpret"))
def segment_rows(rows: jnp.ndarray, seg: jnp.ndarray, n: int,
                 crows: int = 8, cd: int = 512,
                 interpret: bool | None = None) -> jnp.ndarray:
    """Segment-sum of [m, D] rows into [n, D]: ``out[i] = sum_{seg_j == i}
    rows_j`` (f32).  Out-of-range ids drop; duplicate ids add.  ``crows`` /
    ``cd`` tile the (population, feature) axes of the output, rounded up to
    8 sublanes / 128 lanes; ``cd`` shrinks so the m input rows of one
    feature chunk fit a VMEM tile."""
    m, D = rows.shape
    cd = tiling.lanes(min(cd, D))
    cd = min(cd, max(tiling.LANE, tiling.TILE_BYTES // (4 * m)
                     // tiling.LANE * tiling.LANE))
    crows = n if n <= crows else tiling.round_up(crows, tiling.SUBLANE)
    return pl.pallas_call(
        functools.partial(_seg_kernel, m=m),
        grid=(pl.cdiv(n, crows), pl.cdiv(D, cd)),
        in_specs=[tiling.smem_spec(),
                  pl.BlockSpec((m, cd), lambda i, l: (0, l))],
        out_specs=pl.BlockSpec((crows, cd), lambda i, l: (i, l)),
        out_shape=jax.ShapeDtypeStruct((n, D), jnp.float32),
        name="segment_rows",
        interpret=tiling.interpret_mode(interpret),
    )(seg.astype(jnp.int32), rows)
