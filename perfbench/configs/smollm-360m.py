"""Plain reference of SmolLM-360M (HuggingFaceTB/SmolLM-360M), a
Llama-architecture decoder: RMSNorm, grouped-query attention with rotary
positions (rotate-half form), SwiGLU MLP, tied input/output embedding.

Written from the published description in straightforward ``jax.numpy``,
one layer at a time, with no kernel, cache or batching of its own; it
imports nothing of the system under test.  ``dtype`` is the compute type:
float32 is the reference (run under ``highest`` matmul precision), bfloat16
the control.

Conventions of this benchmark, shared with the system under test: the
RMSNorm gain is stored as ``1 + gamma`` with ``gamma`` starting at 0; the
input embedding is scaled by ``sqrt(d_model)``; weights are random from the
seed (fan-in scaled normals, embedding std 0.02).  The parameter tree uses
the layout the system's ``params`` take (``embed``, ``ln_f``, and per-layer
stacks under ``layers``), so one set of weights feeds both.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _dims(m: dict):
    d, h = m["d_model"], m["n_heads"]
    return d, h, m["n_kv_heads"], d // h, m["d_ff"], m["n_layers"], m["vocab"]


def init(key, m: dict):
    """Random float32 weights from ``key``."""
    d, h, kv, hd, f, n_layers, vocab = _dims(m)
    ks = jax.random.split(key, 8)

    def fan_in(k, shape):
        return jax.random.normal(k, shape, jnp.float32) / jnp.sqrt(
            jnp.float32(shape[-2]))

    layers = {
        "ln1": jnp.zeros((n_layers, d)), "ln2": jnp.zeros((n_layers, d)),
        "attn": {"wq": fan_in(ks[1], (n_layers, d, h * hd)),
                 "wk": fan_in(ks[2], (n_layers, d, kv * hd)),
                 "wv": fan_in(ks[3], (n_layers, d, kv * hd)),
                 "wo": fan_in(ks[4], (n_layers, h * hd, d))},
        "mlp": {"w_gate": fan_in(ks[5], (n_layers, d, f)),
                "w_up": fan_in(ks[6], (n_layers, d, f)),
                "w_down": fan_in(ks[7], (n_layers, f, d))},
    }
    return {"embed": jax.random.normal(ks[0], (vocab, d), jnp.float32) * 0.02,
            "ln_f": jnp.zeros((d,)), "layers": layers}


def _rms(x, gamma, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * (1.0 + gamma.astype(jnp.float32))).astype(x.dtype)


def _rope(x, theta):
    """x [B, S, heads, hd] rotated by position (first half / second half)."""
    s, hd = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x1 = x[..., :hd // 2].astype(jnp.float32)
    x2 = x[..., hd // 2:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


def logits(params, tokens, m: dict, dtype=jnp.float32):
    d, h, kv, hd, _, _, _ = _dims(m)
    eps = m["norm_eps"]
    p = jax.tree_util.tree_map(lambda a: a.astype(dtype), params)
    b, s = tokens.shape
    x = p["embed"][tokens] * jnp.asarray(d ** 0.5, dtype)
    causal = jnp.tril(jnp.ones((s, s), bool))

    def layer(x, lp):
        y = _rms(x, lp["ln1"], eps)
        q = (y @ lp["attn"]["wq"]).reshape(b, s, h, hd)
        k = (y @ lp["attn"]["wk"]).reshape(b, s, kv, hd)
        v = (y @ lp["attn"]["wv"]).reshape(b, s, kv, hd)
        q, k = _rope(q, m["rope_theta"]), _rope(k, m["rope_theta"])
        k = jnp.repeat(k, h // kv, axis=2)       # query head i reads kv i // r
        v = jnp.repeat(v, h // kv, axis=2)
        sc = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
        sc = jnp.where(causal, sc / jnp.sqrt(jnp.float32(hd)), -jnp.inf)
        pr = jax.nn.softmax(sc, axis=-1).astype(dtype)
        o = jnp.einsum("bhqk,bkhd->bqhd", pr, v).reshape(b, s, h * hd)
        x = x + o @ lp["attn"]["wo"]
        y = _rms(x, lp["ln2"], eps)
        mlp = lp["mlp"]
        x = x + (jax.nn.silu(y @ mlp["w_gate"]) * (y @ mlp["w_up"])) \
            @ mlp["w_down"]
        return x, None

    x, _ = jax.lax.scan(layer, x, p["layers"])
    return (_rms(x, p["ln_f"], eps) @ p["embed"].T).astype(jnp.float32)


def loss_pair(params, tokens, minority, m: dict, budget: float,
              dtype=jnp.float32):
    """(f, g): next-token cross-entropy over the ordinary tokens, and over
    the minority slice minus ``budget``."""
    lg = logits(params, tokens, m, dtype)[:, :-1]
    tgt, mm = tokens[:, 1:], minority[:, 1:]
    nll = -jnp.take_along_axis(jax.nn.log_softmax(lg, axis=-1),
                               tgt[..., None], axis=-1)[..., 0]
    f = jnp.sum(nll * (1 - mm)) / jnp.maximum(jnp.sum(1 - mm), 1.0)
    g = jnp.sum(nll * mm) / jnp.maximum(jnp.sum(mm), 1.0) - budget
    return f, g


def flops_per_token(m: dict, seq: int) -> float:
    """Forward and backward operations one trained token requires: three
    times the forward's multiply-adds (x2), counting causal attention over
    the (seq + 1) / 2 earlier positions on average and the tied output head;
    recomputation is not counted."""
    d, h, kv, hd, f, n_layers, vocab = _dims(m)
    proj = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f
    attn = 2 * h * hd * (seq + 1) / 2
    fwd = 2 * (n_layers * (proj + attn) + d * vocab)
    return 3.0 * fwd
