"""Pallas TPU kernel: fused EF14 quantization step emitting the bit-packed
wire payload.

    buf   = e + delta
    scale = max|buf|                      (per block)
    codes = round(buf / scale * L)        (L = 2^(b-1) - 1 levels)
    words = pack_b(codes + L)             (32 // b biased lanes per uint32)
    e'    = buf - codes / L * scale

One pass over the VMEM-resident blocks produces the *wire words* directly --
the int8/int32 code tensor of the unfused path never exists, so packed-mode
HBM traffic out of the encode step is the true ``b/32``-word stream and the
EF residual update rides the same block visit.

Packing is a matmul on the MXU.  Lane ``c`` of a block belongs to word
``c // per_word`` at bit offset ``b * (c % per_word)``
(:func:`repro.comm.payloads.pack_codes`).  Biased codes are integers in
[0, 2L] <= 254 and the place values ``2^(b*i)`` are powers of two, so both
are exact in bf16, and each output sums codes with disjoint bit ranges.
Splitting a word into its low and high 16 bits keeps every sum below
2^16, exact in the f32 accumulator.  ``codes[rows, lanes] @ P[lanes, 2W]``
therefore yields both half-words of every word exactly, with no lane-strided
slice (which Mosaic does not lower).  The word rows leave padded to 128
lanes and the per-block scales leave lane-dense (one transpose per tile),
so neither output needs a re-tiling copy at full width.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import tiling
from repro.kernels.quantize_ef import quantize_rows


def place_values(block: int, bits: int) -> jnp.ndarray:
    """[lanes(block), 2 * lanes(W)] bf16: column ``w`` (low half-word) and
    ``lanes(W) + w`` (high half-word) hold the place value of each code
    lane of word ``w``; lanes past ``block`` map to zero bits."""
    from repro.comm.payloads import words_per_block
    per_word, half = 32 // bits, 16 // bits
    wp = tiling.lanes(words_per_block(block, bits))
    c = jax.lax.broadcasted_iota(jnp.int32, (tiling.lanes(block), 2 * wp), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (tiling.lanes(block), 2 * wp), 1)
    lane_in_word = c % per_word
    hi = lane_in_word >= half
    hit = (c < block) & (col == c // per_word + jnp.where(hi, wp, 0))
    power = jnp.left_shift(1, bits * (lane_in_word - jnp.where(hi, half, 0)))
    return jnp.where(hit, power, 0).astype(jnp.bfloat16)


def _kernel(e_ref, d_ref, p_ref, words_ref, scale_ref, enew_ref, col_ref,
            *, bits: int, block: int):
    wp = words_ref.shape[-1]

    def rows(sl):
        buf, codes, scale, v = quantize_rows(e_ref[sl, :], d_ref[sl, :],
                                             bits, block)
        enew_ref[sl, :] = (buf - v).astype(enew_ref.dtype)
        col_ref[sl, :] = jnp.broadcast_to(scale, (scale.shape[0],
                                                  tiling.LANE))
        biased = (codes + float(2 ** (bits - 1) - 1)).astype(jnp.bfloat16)
        halves = jnp.dot(biased, p_ref[...],
                         preferred_element_type=jnp.float32)
        lo = halves[:, :wp].astype(jnp.int32)
        hi = halves[:, wp:].astype(jnp.int32)
        words_ref[sl, :] = jax.lax.bitcast_convert_type(lo | (hi << 16),
                                                        jnp.uint32)
    tiling.for_row_chunks(e_ref.shape[0], rows)
    scale_ref[...] = tiling.rows_to_lanes(col_ref[...])


@functools.partial(jax.jit, static_argnames=("bits", "interpret"))
def quantize_ef_pack(e: jnp.ndarray, delta: jnp.ndarray, bits: int,
                     interpret: bool | None = None):
    """e, delta [..., nblocks, block] -> (words uint32 [..., nblocks, Wp],
    scale f32 [..., nblocks], e_new shaped like e).

    Each block's ``W`` wire words come zero-padded to ``Wp = lanes(W)``
    (a multiple of 128), the row width the flat wire buffers store
    (:mod:`repro.comm.flat`); ``words[..., :W]`` is the
    :func:`repro.comm.payloads.pack_codes` word row."""
    from repro.comm.payloads import PACK_BITS, words_per_block
    if bits not in PACK_BITS:
        raise ValueError(f"bits={bits} not packable; expected {PACK_BITS}")
    e3, lead = tiling.as_batched_rows(e)
    d3, _ = tiling.as_batched_rows(delta)
    L, nblocks, block = e3.shape
    bp = tiling.lanes(block)
    wp = tiling.lanes(words_per_block(block, bits))
    tr = tiling.row_tile(nblocks, block, align=tiling.LANE)
    rows = lambda width: tiling.rows_spec(tr, width)  # noqa: E731
    words, scale, e_new = pl.pallas_call(
        functools.partial(_kernel, bits=bits, block=block),
        grid=(L, pl.cdiv(nblocks, tr)),
        in_specs=[rows(bp), rows(bp),
                  pl.BlockSpec((bp, 2 * wp), lambda c, i: (0, 0))],
        out_specs=[rows(wp),
                   pl.BlockSpec((None, 1, tiling.lanes(tr)),
                                lambda c, i: (c, 0, i)),
                   rows(bp)],
        out_shape=[jax.ShapeDtypeStruct((L, nblocks, wp), jnp.uint32),
                   jax.ShapeDtypeStruct((L, 1, nblocks), jnp.float32),
                   jax.ShapeDtypeStruct(e3.shape, e.dtype)],
        scratch_shapes=[pltpu.VMEM((tiling.lanes(tr), tiling.LANE),
                                   jnp.float32)],
        name="quantize_ef_pack",
        interpret=tiling.interpret_mode(interpret),
    )(e3, d3, place_values(block, bits))
    return (words.reshape(lead + (nblocks, wp)),
            scale.reshape(lead + (nblocks,)), e_new.reshape(e.shape))
