"""Pallas TPU kernel: fused EF14 quantization step.

    v  = Q_b(e + delta)          (per-block max-abs scaled b-bit rounding)
    e' = (e + delta) - v

Fusing the residual update with the quantizer saves one full HBM round-trip
of the (e + delta) buffer per round -- the compression path's dominant memory
term.  One grid step holds a sublane-aligned group of quantization blocks
as the rows of a VMEM tile (:mod:`repro.kernels.tiling`); the per-block
scale is a max along the lane axis.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import tiling


def quantize_rows(e, d, bits: int, block: int):
    """The quantizer on one [rows, lanes] tile whose first ``block`` lanes
    are real: returns (buf, float codes in [-L, L], scale [rows, 1], v).
    Overhang lanes are zeroed first, so they add nothing to the max."""
    lane = jax.lax.broadcasted_iota(jnp.int32, e.shape, 1)
    buf = jnp.where(lane < block, e + d, 0.0)
    scale = jnp.max(jnp.abs(buf), axis=-1, keepdims=True)
    lv = float(2 ** (bits - 1) - 1)
    safe = jnp.where(scale > 0, scale, 1.0)
    codes = jnp.round(buf / safe * lv)
    v = jnp.where(scale > 0, codes / lv * safe, 0.0)
    return buf, codes, scale, v


def _kernel(e_ref, d_ref, v_ref, enew_ref, *, bits: int, block: int):
    def rows(sl):
        buf, _, _, v = quantize_rows(e_ref[sl, :], d_ref[sl, :], bits, block)
        v_ref[sl, :] = v.astype(v_ref.dtype)
        enew_ref[sl, :] = (buf - v).astype(enew_ref.dtype)
    tiling.for_row_chunks(e_ref.shape[0], rows)


@functools.partial(jax.jit, static_argnames=("bits", "interpret"))
def quantize_ef(e: jnp.ndarray, delta: jnp.ndarray, bits: int,
                interpret: bool | None = None):
    """e, delta [..., nblocks, block] -> (v, e_new), both shaped like e."""
    e3, lead = tiling.as_batched_rows(e)
    d3, _ = tiling.as_batched_rows(delta)
    L, nblocks, block = e3.shape
    tr = tiling.row_tile(nblocks, block)
    spec = tiling.rows_spec(tr, tiling.lanes(block))
    v, e_new = pl.pallas_call(
        functools.partial(_kernel, bits=bits, block=block),
        grid=(L, pl.cdiv(nblocks, tr)),
        in_specs=[spec, spec],
        out_specs=[spec, spec],
        out_shape=[jax.ShapeDtypeStruct(e3.shape, e.dtype),
                   jax.ShapeDtypeStruct(e3.shape, e.dtype)],
        name="quantize_ef",
        interpret=tiling.interpret_mode(interpret),
    )(e3, d3)
    return v.reshape(e.shape), e_new.reshape(e.shape)
