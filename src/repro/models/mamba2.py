"""Mamba-2 (SSD, arXiv:2405.21060): attention-free state-space decoder.

Training/prefill use the chunked SSD block decomposition (intra-chunk
quadratic against the 1-semiseparable mask + inter-chunk state recurrence via
lax.scan); decode is the O(1) per-token state update -- which is why this
arch runs the long_500k shape.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import common
from repro.obs import trace as obs_trace
from repro.sharding.partition import shard_act


def _dims(cfg: ModelConfig):
    s = cfg.ssm
    d_inner = s.expand * cfg.d_model
    n_heads = d_inner // s.head_dim
    conv_dim = d_inner + 2 * s.n_groups * s.d_state
    return d_inner, n_heads, conv_dim


def _init_layer(key, cfg: ModelConfig):
    s = cfg.ssm
    d = cfg.d_model
    d_inner, n_heads, conv_dim = _dims(cfg)
    ks = jax.random.split(key, 4)
    d_in_proj = 2 * d_inner + 2 * s.n_groups * s.d_state + n_heads
    return {
        "ln": jnp.zeros((d,)),
        "in_proj": common.dense_init(ks[0], (d, d_in_proj)),
        "conv_w": (jax.random.normal(ks[1], (s.d_conv, conv_dim)) * 0.1),
        "conv_b": jnp.zeros((conv_dim,)),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, n_heads)),
        "D": jnp.ones((n_heads,)),
        "dt_bias": jnp.full((n_heads,), -1.0),
        "gnorm": jnp.zeros((d_inner,)),
        "out_proj": common.dense_init(ks[2], (d_inner, d)),
    }


def init(key, cfg: ModelConfig):
    k0, k1, k2 = jax.random.split(key, 3)
    p = {"embed": common.embed_init(k0, cfg.vocab, cfg.d_model),
         "ln_f": jnp.zeros((cfg.d_model,)),
         "layers": common.stack_layers(k1, cfg.n_layers,
                                       lambda k: _init_layer(k, cfg))}
    if not cfg.tie_embeddings:
        p["lm_head"] = common.dense_init(k2, (cfg.d_model, cfg.vocab))
    return p


def _segsum(a):
    """a [..., Q] -> seg [..., Q, Q]: sum_{j<i<=q} masked lower-tri."""
    Q = a.shape[-1]
    cs = jnp.cumsum(a, axis=-1)
    seg = cs[..., :, None] - cs[..., None, :] + a[..., None, :] - a[..., None, :]
    seg = cs[..., :, None] - cs[..., None, :]
    i = jnp.arange(Q)
    mask = i[:, None] >= i[None, :]
    return jnp.where(mask, seg, -jnp.inf)


def ssd(x, dt, A, Bm, Cm, chunk: int, init_state=None):
    """Chunked SSD.  x [b,l,h,p]; dt [b,l,h]; A [h] (<0); Bm/Cm [b,l,g,n].

    Returns (y [b,l,h,p], final_state [b,h,p,n])."""
    b, l, h, p = x.shape
    g, n = Bm.shape[2], Bm.shape[3]
    rep = h // g
    Bh = jnp.repeat(Bm, rep, axis=2)
    Ch = jnp.repeat(Cm, rep, axis=2)
    Q = min(chunk, l)
    pad = (-l) % Q
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bh = jnp.pad(Bh, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Ch = jnp.pad(Ch, ((0, 0), (0, pad), (0, 0), (0, 0)))
    L = x.shape[1]
    c = L // Q

    a = dt * A[None, None, :]                              # [b,L,h] (negative)
    xdt = x * dt[..., None]
    rs = lambda t, tail: t.reshape((b, c, Q) + tail)
    x_c, a_c = rs(xdt, (h, p)), rs(a, (h,))
    B_c, C_c = rs(Bh, (h, n)), rs(Ch, (h, n))

    a_cs = jnp.cumsum(a_c, axis=2)                         # [b,c,Q,h]
    Lmat = jnp.exp(_segsum(jnp.moveaxis(a_c, 3, 2)))       # [b,c,h,Q,Q]
    scores = jnp.einsum("bcqhn,bckhn->bchqk", C_c, B_c) * Lmat
    y_diag = jnp.einsum("bchqk,bckhp->bcqhp", scores, x_c)

    decay_states = jnp.exp(a_cs[:, :, -1:, :] - a_cs)      # [b,c,Q,h]
    states = jnp.einsum("bcqhn,bcqh,bcqhp->bchpn", B_c, decay_states, x_c)
    chunk_decay = jnp.exp(a_cs[:, :, -1, :])               # [b,c,h]

    S0 = jnp.zeros((b, h, p, n), x.dtype) if init_state is None else init_state

    def step(S, inp):
        dec, st = inp                                      # [b,h], [b,h,p,n]
        S_new = dec[..., None, None] * S + st
        return S_new, S
    S_final, states_prev = jax.lax.scan(
        step, S0, (jnp.moveaxis(chunk_decay, 1, 0), jnp.moveaxis(states, 1, 0)))
    states_prev = jnp.moveaxis(states_prev, 0, 1)          # [b,c,h,p,n]

    out_decay = jnp.exp(a_cs)                              # [b,c,Q,h]
    y_off = jnp.einsum("bcqhn,bchpn,bcqh->bcqhp", C_c, states_prev, out_decay)
    y = (y_diag + y_off).reshape(b, L, h, p)[:, :l]
    return y, S_final


def _causal_conv(x, w, b):
    """x [B,S,C]; w [K,C] depthwise causal conv + silu."""
    K = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    out = sum(xp[:, i: i + x.shape[1]] * w[i] for i in range(K))
    return jax.nn.silu(out + b)


def _mixer(lp, cfg: ModelConfig, x, conv_cache=None, ssm_state=None,
           decode: bool = False):
    """Returns (y, new_conv_cache, new_ssm_state)."""
    s = cfg.ssm
    d_inner, n_heads, conv_dim = _dims(cfg)
    B_, S_, _ = x.shape
    proj = x @ lp["in_proj"]
    z, xBC, dt_raw = jnp.split(proj, [d_inner, d_inner + conv_dim], axis=-1)
    dt = jax.nn.softplus(dt_raw + lp["dt_bias"])           # [B,S,h]
    A = -jnp.exp(lp["A_log"])

    if decode:
        # conv over the cached window + current input
        win = jnp.concatenate([conv_cache, xBC], axis=1)   # [B, K, conv_dim]
        conv_out = jax.nn.silu(
            jnp.sum(win * lp["conv_w"], axis=1, keepdims=True) + lp["conv_b"])
        new_conv = win[:, 1:]
    else:
        conv_out = _causal_conv(xBC, lp["conv_w"], lp["conv_b"])
        new_conv = jnp.pad(xBC, ((0, 0), (max(s.d_conv - 1 - S_, 0), 0),
                                 (0, 0)))[:, -(s.d_conv - 1):]
    xs, B0, C0 = jnp.split(conv_out,
                           [d_inner, d_inner + s.n_groups * s.d_state], axis=-1)
    xh = xs.reshape(B_, -1, n_heads, s.head_dim)
    Bm = B0.reshape(B_, -1, s.n_groups, s.d_state)
    Cm = C0.reshape(B_, -1, s.n_groups, s.d_state)

    if decode:
        rep = n_heads // s.n_groups
        Bh = jnp.repeat(Bm[:, 0], rep, axis=1)             # [B,h,n]
        Ch = jnp.repeat(Cm[:, 0], rep, axis=1)
        dt0 = dt[:, 0]                                     # [B,h]
        dec = jnp.exp(dt0 * A[None])                       # [B,h]
        upd = jnp.einsum("bh,bhp,bhn->bhpn", dt0, xh[:, 0], Bh)
        S_new = dec[..., None, None] * ssm_state + upd
        y = jnp.einsum("bhn,bhpn->bhp", Ch, S_new)[:, None]
    else:
        y, S_new = ssd(xh, dt, A, Bm, Cm, s.chunk, init_state=ssm_state)

    y = y + lp["D"][None, None, :, None] * xh[:, : y.shape[1]]
    y = y.reshape(B_, -1, d_inner)
    y = y * jax.nn.silu(z)
    y = common.rms_norm(y, lp["gnorm"], cfg.norm_eps)
    return y @ lp["out_proj"], new_conv, S_new


def _embed(params, tokens):
    with obs_trace.stage("model.embed"):
        return params["embed"][tokens]


def _logits(params, cfg, h):
    with obs_trace.stage("model.head"):
        h = common.rms_norm(h, params["ln_f"], cfg.norm_eps)
        w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        return shard_act(h @ w, "batch", None, "vocab")


def _block(lp, cfg: ModelConfig, h, **kw):
    """One layer: the pre-norm, the whole SSD mixer (in-projection, causal
    conv, ``ssd``, gate and out-projection) and the residual add.  Returns
    (h, new_conv_cache, new_ssm_state)."""
    with obs_trace.stage("model.mixer"):
        y, conv, ssm_state = _mixer(
            lp, cfg, common.rms_norm(h, lp["ln"], cfg.norm_eps), **kw)
        return h + y, conv, ssm_state


def forward(params, cfg: ModelConfig, tokens):
    h = shard_act(_embed(params, tokens), "batch", None, None)

    def body(h, lp):
        h, _, _ = _block(lp, cfg, h)
        return h, None
    body_fn = jax.checkpoint(body) if cfg.remat else body
    h, _ = jax.lax.scan(body_fn, h, params["layers"])
    return _logits(params, cfg, h)


class ServeCache(NamedTuple):
    conv: jnp.ndarray    # [L, B, K-1, conv_dim]
    ssm: jnp.ndarray     # [L, B, h, p, n]


def init_decode_cache(cfg: ModelConfig, batch: int, cache_len: int, params=None):
    s = cfg.ssm
    d_inner, n_heads, conv_dim = _dims(cfg)
    return ServeCache(
        jnp.zeros((cfg.n_layers, batch, s.d_conv - 1, conv_dim)),
        jnp.zeros((cfg.n_layers, batch, n_heads, s.head_dim, s.d_state)))


def prefill(params, cfg: ModelConfig, tokens, cache_len: int):
    h = _embed(params, tokens)

    def body(h, lp):
        h, conv, ssm_state = _block(lp, cfg, h)
        return h, (conv, ssm_state)
    h, (convs, ssms) = jax.lax.scan(body, h, params["layers"])
    return _logits(params, cfg, h[:, -1:]), ServeCache(convs, ssms)


def decode_step(params, cfg: ModelConfig, token, cache: ServeCache, pos):
    h = _embed(params, token)

    def body(h, xs):
        lp, conv, ssm_state = xs
        h, conv_new, ssm_new = _block(lp, cfg, h, conv_cache=conv,
                                      ssm_state=ssm_state, decode=True)
        return h, (conv_new, ssm_new)
    h, (convs, ssms) = jax.lax.scan(body, h, (params["layers"], cache.conv, cache.ssm))
    return _logits(params, cfg, h), ServeCache(convs, ssms)
