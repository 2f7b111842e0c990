"""Plain reference of Mamba-2 130M (state-spaces/mamba2-130m, SSD,
arXiv:2405.21060): attention-free decoder of Mamba-2 mixers.

Per layer: RMSNorm; ``in_proj`` into the gate ``z``, the convolved stream
``xBC`` and the step ``dt``; a depthwise causal convolution of width
``d_conv`` with SiLU; the selective state-space map with a scalar decay per
head, ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``, ``y_t = C_t S_t +
D x_t``; the gate ``y * silu(z)``, a gated RMSNorm and ``out_proj``; tied
output head.

The state-space map is computed in its plain quadratic (dual) form over
the whole sequence: ``y_t = sum_{s<=t} (C_t . B_s) exp(a_{s+1} + ... +
a_t) dt_s x_s`` with ``a = dt A``, one layer at a time, in
straightforward ``jax.numpy``; it imports nothing of the system under
test.  ``dtype`` is the compute type: float32 is the reference (run under
``highest`` matmul precision), bfloat16 the control.

Conventions shared with the system under test: norm gains are stored as
``1 + gamma`` with ``gamma`` starting at 0; ``A = -exp(A_log)`` with
``A_log = log(1..16)`` spread over the heads; ``dt`` starts log-uniform in
[1e-3, 1e-1] through ``dt_bias``; other weights are fan-in scaled normals
from the seed (convolution std 0.1, embedding std 0.02).  The parameter
tree uses the layout of the system's ``params``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _dims(m: dict):
    s = m["ssm"]
    d_inner = s["expand"] * m["d_model"]
    heads = d_inner // s["head_dim"]
    gn = s["n_groups"] * s["d_state"]
    conv_dim = d_inner + 2 * gn
    return d_inner, heads, gn, conv_dim


def init(key, m: dict):
    """Random float32 weights from ``key``."""
    s = m["ssm"]
    d, n_layers = m["d_model"], m["n_layers"]
    d_inner, heads, gn, conv_dim = _dims(m)
    ks = jax.random.split(key, 6)

    def fan_in(k, shape):
        return jax.random.normal(k, shape, jnp.float32) / jnp.sqrt(
            jnp.float32(shape[-2]))

    dt = jnp.exp(jax.random.uniform(ks[4], (n_layers, heads), jnp.float32,
                                    jnp.log(1e-3), jnp.log(1e-1)))
    layers = {
        "ln": jnp.zeros((n_layers, d)),
        "in_proj": fan_in(ks[1], (n_layers, d,
                                  2 * d_inner + 2 * gn + heads)),
        "conv_w": jax.random.normal(ks[2], (n_layers, s["d_conv"], conv_dim),
                                    jnp.float32) * 0.1,
        "conv_b": jnp.zeros((n_layers, conv_dim)),
        "A_log": jnp.broadcast_to(jnp.log(jnp.linspace(1.0, 16.0, heads)),
                                  (n_layers, heads)),
        "D": jnp.ones((n_layers, heads)),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),     # softplus^-1(dt)
        "gnorm": jnp.zeros((n_layers, d_inner)),
        "out_proj": fan_in(ks[3], (n_layers, d_inner, d)),
    }
    return {"embed": jax.random.normal(ks[0], (m["vocab"], d),
                                       jnp.float32) * 0.02,
            "ln_f": jnp.zeros((d,)), "layers": layers}


def _rms(x, gamma, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * (1.0 + gamma.astype(jnp.float32))).astype(x.dtype)


def _mixer(lp, x, m: dict, dtype):
    s = m["ssm"]
    d_inner, heads, gn, conv_dim = _dims(m)
    b, t, _ = x.shape
    p, n, k = s["head_dim"], s["d_state"], s["d_conv"]
    proj = x @ lp["in_proj"]
    z = proj[..., :d_inner]
    xbc = proj[..., d_inner:d_inner + conv_dim]
    dt = jax.nn.softplus((proj[..., d_inner + conv_dim:]
                          + lp["dt_bias"]).astype(jnp.float32))  # [b, t, h]
    pad = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    conv = sum(pad[:, i:i + t] * lp["conv_w"][i] for i in range(k))
    conv = jax.nn.silu(conv + lp["conv_b"])
    xs = conv[..., :d_inner].reshape(b, t, heads, p)
    bm = conv[..., d_inner:d_inner + gn].reshape(b, t, s["n_groups"], n)
    cm = conv[..., d_inner + gn:].reshape(b, t, s["n_groups"], n)
    rep = heads // s["n_groups"]
    bm = jnp.repeat(bm, rep, axis=2)                       # [b, t, h, n]
    cm = jnp.repeat(cm, rep, axis=2)
    a = dt * -jnp.exp(lp["A_log"].astype(jnp.float32))    # [b, t, h] < 0
    cs = jnp.cumsum(a, axis=1)
    seg = cs[:, :, None, :] - cs[:, None, :, :]            # [b, t, s, h]
    causal = jnp.tril(jnp.ones((t, t), bool))[None, :, :, None]
    decay = jnp.where(causal, jnp.exp(jnp.where(causal, seg, 0.0)), 0.0)
    cb = jnp.einsum("bthn,bshn->btsh", cm, bm).astype(jnp.float32)
    w = (cb * decay * dt[:, None, :, :]).astype(dtype)     # weight of x_s
    y = jnp.einsum("btsh,bshp->bthp", w, xs)
    y = y + lp["D"][None, None, :, None] * xs
    y = y.reshape(b, t, d_inner) * jax.nn.silu(z)
    y = _rms(y, lp["gnorm"], m["norm_eps"])
    return y @ lp["out_proj"]


def logits(params, tokens, m: dict, dtype=jnp.float32):
    p = jax.tree_util.tree_map(lambda a: a.astype(dtype), params)
    eps = m["norm_eps"]
    x = p["embed"][tokens]

    def layer(x, lp):
        return x + _mixer(lp, _rms(x, lp["ln"], eps), m, dtype), None

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
    times the forward's multiply-adds (x2) of the projections, the
    convolution, the recurrent state-space update and read-out (decay,
    input outer product, C S), the gate and the tied output head;
    recomputation is not counted."""
    s = m["ssm"]
    d, n_layers = m["d_model"], m["n_layers"]
    d_inner, heads, gn, conv_dim = _dims(m)
    proj = d * (2 * d_inner + 2 * gn + heads) + d_inner * d
    conv = s["d_conv"] * conv_dim
    ssm = 3 * heads * s["head_dim"] * s["d_state"]
    fwd = 2 * (n_layers * (proj + conv + ssm) + d * m["vocab"])
    return 3.0 * fwd
