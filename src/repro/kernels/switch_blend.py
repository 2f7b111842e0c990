"""Pallas TPU kernel: fused soft-switching gradient blend.

    nu = (1 - sigma) * grad_f + sigma * grad_g

sigma is the round-constant switching weight (scalar, SMEM).  Fusion avoids
materializing the blended pytree as a third full-model buffer per local step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import tiling


def _kernel(sigma_ref, gf_ref, gg_ref, out_ref):
    s = sigma_ref[0]
    out_ref[...] = (1.0 - s) * gf_ref[...] + s * gg_ref[...]


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def switch_blend(gf: jnp.ndarray, gg: jnp.ndarray, sigma: jnp.ndarray,
                 block: int = 4096, interpret: bool | None = None):
    """gf, gg flat [d]; sigma scalar -> blended [d].  The buffers are viewed
    as [rows, block] (block rounded up to 128 lanes, zero-padded)."""
    d = gf.shape[0]
    block = tiling.lanes(min(block, d))
    pad = (-d) % block
    gf2 = jnp.pad(gf, (0, pad)).reshape(-1, block)
    gg2 = jnp.pad(gg, (0, pad)).reshape(-1, block)
    nrows = gf2.shape[0]
    tr = tiling.row_tile(nrows, block)
    spec = pl.BlockSpec((tr, block), lambda i: (i, 0))
    out = pl.pallas_call(
        _kernel,
        grid=(pl.cdiv(nrows, tr),),
        in_specs=[tiling.smem_spec(), spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((nrows, block), gf.dtype),
        name="switch_blend",
        interpret=tiling.interpret_mode(interpret),
    )(sigma.reshape(1).astype(jnp.float32), gf2, gg2)
    return out.reshape(-1)[:d]
