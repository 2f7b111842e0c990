"""Flash-decode over a length-sharded KV cache (beyond-paper serving path).

GSPMD handles softmax over a sharded axis correctly but conservatively (it
may materialize full score rows).  This shard_map variant computes per-shard
partial (max, sum, weighted-V) statistics and merges them with a stable
logsumexp combine -- one psum of O(B*H*(hd+2)) instead of score-row
resharding.  Used when a mesh is active and the cache length is sharded over
``model``; falls back to dense attention otherwise.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.sharding import partition


def _partial_attend(q, k, v, valid):
    """One shard's contribution.  q [B,KV,R,hd]; k,v [B,S_loc,KV,hd];
    valid [S_loc] bool.  Returns (m, l, o) partial stats."""
    scale = 1.0 / jnp.sqrt(q.shape[-1]).astype(jnp.float32)
    s = jnp.einsum("bkrh,bskh->bkrs", q.astype(jnp.float32) * scale,
                   k.astype(jnp.float32))
    s = jnp.where(valid[None, None, None, :], s, -jnp.inf)
    m = jnp.max(s, axis=-1)                                   # [B,KV,R]
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(s - m_safe[..., None])
    p = jnp.where(valid[None, None, None, :], p, 0.0)
    l = jnp.sum(p, axis=-1)                                   # [B,KV,R]
    o = jnp.einsum("bkrs,bskh->bkrh", p, v.astype(jnp.float32))
    return m, l, o


def flash_decode_attend(q, k_cache, v_cache, kv_valid, mesh=None,
                        axis: str = "model"):
    """q [B,1,H,hd]; caches [B,S,KV,hd] length-sharded over ``axis``;
    kv_valid [S] bool.  Returns [B,1,H*hd] attention output."""
    B, _, H, hd = q.shape
    KV = k_cache.shape[2]
    R = H // KV
    qg = q[:, 0].reshape(B, KV, R, hd)
    mesh = mesh or partition.current_mesh()
    if mesh is None or axis not in mesh.axis_names:
        # dense fallback
        m, l, o = _partial_attend(qg, k_cache, v_cache, kv_valid)
        out = o / jnp.maximum(l, 1e-30)[..., None]
        return out.reshape(B, 1, H * hd).astype(q.dtype)

    def kernel(qg_, k_, v_, valid_):
        m, l, o = _partial_attend(qg_, k_, v_, valid_)
        # stable logsumexp merge across shards
        m_glob = jax.lax.pmax(m, axis)
        m_safe = jnp.where(jnp.isfinite(m_glob), m_glob, 0.0)
        corr = jnp.exp(jnp.where(jnp.isfinite(m), m, -jnp.inf) - m_safe)
        l_glob = jax.lax.psum(l * corr, axis)
        o_glob = jax.lax.psum(o * corr[..., None], axis)
        return o_glob / jnp.maximum(l_glob, 1e-30)[..., None]

    spec_kv = P(None, axis, None, None)
    out = jax.shard_map(
        kernel, mesh=mesh,
        in_specs=(P(), spec_kv, spec_kv, P(axis)),
        out_specs=P(),
        check_vma=False,
    )(qg, k_cache, v_cache, kv_valid)
    return out.reshape(B, 1, H * hd).astype(q.dtype)
