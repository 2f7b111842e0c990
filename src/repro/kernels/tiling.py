"""Tile geometry shared by the Pallas kernels.

Mosaic (the TPU kernel compiler) accepts a VMEM block only when its last
dim is a multiple of 128 lanes and its second-minor dim a multiple of 8
sublanes, or either equals the full array dim.  Every kernel here tiles
rows in sublane groups of 8 and pads the lane dim of a block up to a
multiple of 128 *in the BlockSpec*: the block may overhang the array's
last dim, the DMA leaves the overhang undefined on reads and drops it on
writes, and the kernel masks those lanes (``lane < block``) before any
reduction.  No padded copy of the operand is made in HBM.

Per-client scalars (aggregation weights, segment ids) live in SMEM as a
whole array indexed by ``pl.program_id``, never in 1-wide VMEM blocks.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128
SUBLANE = 8
TILE_BYTES = 512 * 1024     # one f32 VMEM operand tile (double-buffered)


def round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def lanes(width: int) -> int:
    """Lane-aligned VMEM width holding ``width`` elements."""
    return round_up(max(width, 1), LANE)


def row_tile(rows: int, width: int, budget: int = TILE_BYTES,
             cap: int | None = None, align: int = SUBLANE) -> int:
    """Rows per grid step for a [rows, width] f32 operand: a multiple of
    ``align`` (8 sublanes, or 128 where the rows' scales travel lane-dense)
    whose lane-padded tile fits ``budget`` (and ``cap``), or all ``rows``
    when they fit in one tile (a block equal to the full dim is legal)."""
    fit = max(align, budget // (4 * lanes(width)) // align * align)
    if cap is not None:
        fit = min(fit, max(align, cap // align * align))
    return rows if rows <= fit else fit


def for_row_chunks(rows: int, body, chunk: int = SUBLANE) -> None:
    """Call ``body(rows_slice)`` over a tile's rows in chunks of ``chunk``
    rows (a multiple of 8 sublanes), then once over the rows left; one call
    over all rows when ``rows`` is not a multiple of 8.  Mosaic emits
    straight-line code for every vreg an op touches, so looping over small
    chunks keeps a kernel's code and compile time small while its DMA tile
    stays large."""
    if rows % SUBLANE:
        body(pl.ds(0, rows))
        return
    n, tail = divmod(rows, chunk)

    def step(i, carry):
        body(pl.ds(pl.multiple_of(i * chunk, chunk), chunk))
        return carry
    if n:
        jax.lax.fori_loop(0, n, step, 0)
    if tail:
        body(pl.ds(n * chunk, tail))


def rows_to_lanes(cols):
    """[R, 128] -> [1, R] (R a multiple of 128): lane 0 of every row laid
    along the lanes, by one transpose.  Per-block scalars computed as a
    column of a row tile leave the kernel lane-dense this way, so the
    [.., nblocks] array they form needs no re-tiling."""
    return jnp.transpose(cols)[0:1, :]


def lanes_to_rows(row):
    """[1, R] -> [R, 128] (R a multiple of 128), row i holding ``row[0, i]``
    in every lane: the inverse of :func:`rows_to_lanes`."""
    return jnp.transpose(jnp.broadcast_to(row, (LANE, row.shape[-1])))


def as_batched_rows(x):
    """View [..., rows, width] as [L, rows, width] (L = product of the
    leading dims, 1 without any).  Merging the leading dims into the row
    axis instead would force the TPU compiler to re-tile the whole array;
    folding them into a grid axis keeps every view a cheap bitcast."""
    lead = x.shape[:-2]
    return x.reshape((math.prod(lead),) + x.shape[-2:]), lead


def rows_spec(tr: int, width: int) -> pl.BlockSpec:
    """[tr, width] tiles of one batch entry of an [L, rows, width] array
    on the (batch, row-tile) grid."""
    return pl.BlockSpec((None, tr, width), lambda c, i: (c, i, 0))


def interpret_mode(interpret: bool | None) -> bool:
    """Kernels run compiled on a TPU and in the Pallas interpreter on any
    other backend, unless the caller pins the mode."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def smem_spec() -> pl.BlockSpec:
    """A whole small array in SMEM (per-client scalars)."""
    return pl.BlockSpec(memory_space=pltpu.SMEM)
