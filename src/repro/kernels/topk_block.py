"""Pallas TPU kernel: block-wise magnitude top-k (the TPU-native Top-K).

One grid step holds a sublane-aligned group of blocks as the rows of a VMEM
tile and selects each row's k largest-|x| entries by k rounds of masked
argmax along the lane axis (k << block, so this is k cheap VPU reductions
instead of a full sort; global Top-K over R^d does not map to the TPU memory
hierarchy -- DESIGN.md §3).  Ties go to the lowest offset, as in
``lax.top_k``.  Emits the packed (values, indices) payload used by the
wire-compressed collective path (core/packing.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import tiling


def _kernel(x_ref, vals_ref, idx_ref, *, k: int, block: int):
    def rows(sl):
        x = x_ref[sl, :].astype(jnp.float32)          # [rows, lanes]
        lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
        out_shape = (x.shape[0], vals_ref.shape[1])
        slot = jax.lax.broadcasted_iota(jnp.int32, out_shape, 1)
        absx = jnp.where(lane < block, jnp.abs(x), -jnp.inf)

        def pick(t, carry):
            absm, vals, idxs = carry
            top = jnp.max(absm, axis=-1, keepdims=True)
            j = jnp.min(jnp.where(absm == top, lane, x.shape[-1]), axis=-1,
                        keepdims=True)                # first max: [rows, 1]
            hit = lane == j
            xv = jnp.sum(jnp.where(hit, x, 0.0), axis=-1, keepdims=True)
            vals = jnp.where(slot == t, xv, vals)
            idxs = jnp.where(slot == t, j, idxs)
            return jnp.where(hit, -jnp.inf, absm), vals, idxs

        _, vals, idxs = jax.lax.fori_loop(
            0, k, pick, (absx, jnp.zeros(out_shape, jnp.float32),
                         jnp.zeros(out_shape, jnp.int32)))
        vals_ref[sl, :] = vals.astype(vals_ref.dtype)
        idx_ref[sl, :] = idxs
    tiling.for_row_chunks(x_ref.shape[0], rows)


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def block_topk(x: jnp.ndarray, k: int, interpret: bool | None = None):
    """x [..., nblocks, block] -> (values [..., nblocks, kp], indices int32
    [..., nblocks, kp]): the k selected entries of each block in slots
    ``[:k]``, zero-padded to ``kp = lanes(k)`` slots (a multiple of 128),
    the row width the flat wire buffers store (:mod:`repro.comm.flat`)."""
    x3, lead = tiling.as_batched_rows(x)
    L, nblocks, block = x3.shape
    tr = tiling.row_tile(nblocks, block)
    kp = tiling.lanes(k)
    out = tiling.rows_spec(tr, kp)
    vals, idx = pl.pallas_call(
        functools.partial(_kernel, k=k, block=block),
        grid=(L, pl.cdiv(nblocks, tr)),
        in_specs=[tiling.rows_spec(tr, tiling.lanes(block))],
        out_specs=[out, out],
        out_shape=[jax.ShapeDtypeStruct((L, nblocks, kp), x.dtype),
                   jax.ShapeDtypeStruct((L, nblocks, kp), jnp.int32)],
        name="block_topk",
        interpret=tiling.interpret_mode(interpret),
    )(x3)
    return (vals.reshape(lead + (nblocks, kp)),
            idx.reshape(lead + (nblocks, kp)))
