"""Pallas TPU kernel: block-wise magnitude top-k (the TPU-native Top-K).

One grid step holds a group of blocks as the rows of a VMEM tile and
selects each row's k largest-|x| entries exactly, without a sort (global
Top-K over R^d does not map to the TPU memory hierarchy -- DESIGN.md §3):

1. **Threshold.**  For a non-negative float the int32 bit pattern orders as
   the value, so ``key = bitcast(|x|, int32)`` (lanes past ``block``: -1).
   Each row's k-th largest key ``T`` is found bit by bit, high bit first:
   31 steps of one compare and one per-row count of ``key >= T | bit``.
2. **Select.**  Every lane with ``key > T`` is kept, and the remaining
   ``k - count(key > T)`` slots go to the lanes with ``key == T``, lowest
   offset first -- ``lax.top_k``'s tie rule.
3. **Compact.**  A kept lane's slot is the count of kept lanes before it
   (one log-step roll-and-add scan gives the prefix counts of both the
   ``>`` and the ``==`` lanes).  Values and offsets move left to their
   slots in log2 stages of lane rolls, shifting by the bits of
   ``lane - slot``, low bit first: kept lanes keep their order, so no two
   ever land on one lane.

Rows go through in chunks of ``CHUNK`` rows, so the per-row counts of one
step are independent reductions that pipeline.  The selected set is the
same as ``lax.top_k(|x|, k)`` (values bitwise equal); the slots hold it in
ascending offset, not descending magnitude.  Emits the packed (values,
offsets) payload of the wire-compressed collective path (core/packing.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import tiling

CHUNK = 32          # rows per vector op: four sublane groups


def _prefix_sum(c, lane):
    """Inclusive prefix sum along the lanes of an int32 [rows, W] array."""
    s = 1
    while s < c.shape[-1]:
        c = c + jnp.where(lane >= s, pltpu.roll(c, s, 1), 0)
        s *= 2
    return c


def _kernel(x_ref, vals_ref, idx_ref, *, k: int, block: int):
    kp = vals_ref.shape[1]

    def rows(sl):
        x = x_ref[sl, :].astype(jnp.float32)          # [rows, W]
        W = x.shape[-1]
        lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
        key = jnp.where(lane < block,
                        jax.lax.bitcast_convert_type(jnp.abs(x), jnp.int32),
                        -1)

        def bisect(i, t):                             # t: [rows, 1]
            mid = t | jnp.left_shift(jnp.int32(1), 30 - i)
            cnt = jnp.sum((key >= mid).astype(jnp.int32), axis=-1,
                          keepdims=True)
            return jnp.where(cnt >= k, mid, t)
        t = jax.lax.fori_loop(0, 31, bisect,
                              jnp.zeros((x.shape[0], 1), jnp.int32))

        gt, eq = key > t, key == t
        # prefix counts of the > lanes (low 16 bits) and == lanes (high)
        c = gt.astype(jnp.int32) + jnp.left_shift(eq.astype(jnp.int32), 16)
        inc = _prefix_sum(c, lane)
        exc = inc - c
        need = k - (inc[:, W - 1:W] & 0xFFFF)          # tie slots to fill
        tie_rank = jnp.right_shift(exc, 16)
        kept = gt | (eq & (tie_rank < need))
        slot = (exc & 0xFFFF) + jnp.minimum(tie_rank, need)
        # only kept lanes move; a lane one leaves keeps a stale value, which
        # a later arrival overwrites or which lies past slot k
        dist = jnp.where(kept, lane - slot, 0)         # at most block - k
        v = x
        s = 1
        while s <= block - k:
            rd, rv = pltpu.roll(dist, W - s, 1), pltpu.roll(v, W - s, 1)
            arrive = (rd & s) != 0
            dist = jnp.where(arrive, rd, jnp.where((dist & s) != 0, 0, dist))
            v = jnp.where(arrive, rv, v)
            s *= 2
        # a fresh iota: Mosaic cannot slice the [rows, W] one's lanes
        out = jax.lax.broadcasted_iota(jnp.int32, (x.shape[0], kp), 1)
        vals_ref[sl, :] = jnp.where(out < k, v[:, :kp], 0.0).astype(
            vals_ref.dtype)
        idx_ref[sl, :] = jnp.where(out < k, out + dist[:, :kp], 0)
    tiling.for_row_chunks(x_ref.shape[0], rows, CHUNK)


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def block_topk(x: jnp.ndarray, k: int, interpret: bool | None = None):
    """x [..., nblocks, block] -> (values [..., nblocks, kp], offsets int32
    [..., nblocks, kp]): the k selected entries of each block in slots
    ``[:k]`` in ascending offset, zero-padded to ``kp = lanes(k)`` slots (a
    multiple of 128), the row width the flat wire buffers store
    (:mod:`repro.comm.flat`)."""
    x3, lead = tiling.as_batched_rows(x)
    L, nblocks, block = x3.shape
    tr = tiling.row_tile(nblocks, block, align=CHUNK)
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
