"""Pallas TPU kernel: fused unpack-multiply-add payload aggregation.

    acc[b] = sum_j  weight_j * scale_{j,b} / L * unpack(words_{j,b})

The client axis rides the *inner* grid dimension so each output tile is
revisited consecutively (TPU output-revisit rule) and accumulates in VMEM:
the bit-packed uint32 words are the only client-indexed HBM traffic -- the
per-client dense code tensors of the scan-based aggregation never
materialize.  One grid step holds a group of blocks as rows; the client
weights sit in SMEM and the per-block scales arrive lane-dense (one
transpose per tile turns them into the rows' column).

Unpacking is the inverse of the MXU pack in :mod:`.quantize_ef_pack`: the
four bytes of every word are exact in bf16, and a one-hot matmul
``bytes[rows, 4W] @ E[4W, lanes]`` copies to lane ``c`` the byte that holds
its code (word ``c // per_word``, bit offset ``b * (c % per_word)``,
:func:`repro.comm.payloads.unpack_codes`).  A per-lane shift and mask then
leave the biased code, with no interleaving reshape.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import tiling


def byte_spread(block: int, bits: int) -> jnp.ndarray:
    """[4 * lanes(W), lanes(block)] bf16 one-hot: row ``byte * lanes(W) +
    w`` feeds lane ``c`` iff ``c`` is a code lane of word ``w`` stored in
    that byte."""
    from repro.comm.payloads import words_per_block
    per_word = 32 // bits
    wp = tiling.lanes(words_per_block(block, bits))
    shape = (4 * wp, tiling.lanes(block))
    r = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    c = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    byte = bits * (c % per_word) // 8
    hit = (c < block) & (r == byte * wp + c // per_word)
    return hit.astype(jnp.bfloat16)


def _kernel(weight_ref, words_ref, scale_ref, spread_ref, acc_ref, col_ref,
            *, bits: int):
    per_word = 32 // bits
    levels = 2 ** (bits - 1) - 1
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros(acc_ref.shape, acc_ref.dtype)

    col_ref[...] = tiling.lanes_to_rows(scale_ref[...])
    w = weight_ref[j] / float(levels)

    def rows(sl):
        words = jax.lax.bitcast_convert_type(words_ref[sl, :], jnp.int32)
        by = [jax.lax.shift_right_logical(words, 8 * i) & 0xFF
              for i in range(4)]
        by = jnp.concatenate(by, axis=-1).astype(jnp.float32) \
            .astype(jnp.bfloat16)
        spread = jnp.dot(by, spread_ref[...],
                         preferred_element_type=jnp.float32)
        lane = jax.lax.broadcasted_iota(jnp.int32, spread.shape, 1)
        shift = bits * (lane % per_word) % 8
        codes = jax.lax.shift_right_logical(spread.astype(jnp.int32),
                                            shift) & ((1 << bits) - 1)
        vals = codes.astype(jnp.float32) - float(levels)
        acc_ref[sl, :] += (w * col_ref[sl, 0:1]) * vals
    tiling.for_row_chunks(acc_ref.shape[0], rows)


@functools.partial(jax.jit, static_argnames=("bits", "block", "interpret"))
def unpack_mma(words: jnp.ndarray, scale: jnp.ndarray, weight: jnp.ndarray,
               bits: int, block: int, interpret: bool | None = None):
    """words [n, nblocks, W or Wp] uint32 (word rows as packed, or padded
    to 128 lanes as the flat wire buffers store them), scale [n, nblocks]
    f32, weight [n] f32 -> weighted payload-domain sum [nblocks, block]
    f32."""
    from repro.comm.payloads import PACK_BITS, words_per_block
    if bits not in PACK_BITS:
        raise ValueError(f"bits={bits} not packable; expected {PACK_BITS}")
    n, nblocks, _ = words.shape
    bp = tiling.lanes(block)
    wp = tiling.lanes(words_per_block(block, bits))
    tr = tiling.row_tile(nblocks, block, align=tiling.LANE)
    return pl.pallas_call(
        functools.partial(_kernel, bits=bits),
        grid=(pl.cdiv(nblocks, tr), n),
        in_specs=[tiling.smem_spec(),
                  pl.BlockSpec((None, tr, wp), lambda i, j: (j, i, 0)),
                  pl.BlockSpec((None, 1, tiling.lanes(tr)),
                               lambda i, j: (j, 0, i)),
                  pl.BlockSpec((4 * wp, bp), lambda i, j: (0, 0))],
        out_specs=pl.BlockSpec((tr, bp), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nblocks, block), jnp.float32),
        scratch_shapes=[pltpu.VMEM((tiling.lanes(tr), tiling.LANE),
                                   jnp.float32)],
        name="unpack_mma",
        interpret=tiling.interpret_mode(interpret),
    )(weight.astype(jnp.float32), words,
      scale.astype(jnp.float32).reshape(n, 1, nblocks),
      byte_spread(block, bits))
