"""Pallas kernel micro-benchmarks (interpret mode on CPU -- correctness-
oriented timing; TPU wall-times require real hardware)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, timed
from repro.kernels import ref
from repro.kernels.quantize_ef import quantize_ef
from repro.kernels.topk_block import block_topk


def kernel_topk():
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (8, 256))
    us, (v, i) = timed(lambda a: block_topk(a, 26), x)
    v = v[:, :26]                   # slot rows come padded to 128 lanes
    vr, ir = ref.block_topk_ref(x, 26)
    err = float(np.max(np.abs(np.sort(np.asarray(v)) - np.sort(np.asarray(vr)))))
    emit("kernel_topk_block_8x256_k26", us, f"max_err_vs_ref={err:.2e}")


def kernel_quantize_ef():
    key = jax.random.PRNGKey(1)
    e = jax.random.normal(key, (8, 256))
    d = jax.random.normal(jax.random.fold_in(key, 1), (8, 256))
    us, (v, en) = timed(lambda a, b: quantize_ef(a, b, 8), e, d)
    vr, enr = ref.quantize_ef_ref(e, d, 8)
    err = float(np.max(np.abs(np.asarray(v) - np.asarray(vr))))
    emit("kernel_quantize_ef_8x256_b8", us, f"max_err_vs_ref={err:.2e}")


def kernel_vs_xla_topk():
    """Derived: jax.lax.top_k reference timing for the same job."""
    key = jax.random.PRNGKey(2)
    x = jax.random.normal(key, (8, 256))
    fn = jax.jit(lambda a: ref.block_topk_ref(a, 26))
    us, _ = timed(fn, x)
    emit("xla_topk_reference_8x256_k26", us, "baseline=jax.lax.top_k")


ALL = [kernel_topk, kernel_quantize_ef, kernel_vs_xla_topk]
