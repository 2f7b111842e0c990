"""Dense decoder-only transformer: GQA + RoPE (+ optional qk-norm,
local:global sliding-window patterns, interleaved cross-attention for VLMs).

Covers: qwen3-4b, minitron-4b, smollm-360m, gemma3-4b, llama-3.2-vision-90b.

Homogeneous stacks scan over layers.  Patterned stacks (gemma3 5:1,
vision cross-attn interleave) scan over *pattern blocks* -- one block is one
pattern period (e.g. [local x5, global] or [self x4, cross]) -- with any
remainder layers unrolled.  This keeps the HLO O(period) instead of O(L).
"""
from __future__ import annotations

from typing import List, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention, common
from repro.obs import trace as obs_trace
from repro.sharding.partition import shard_act


def _is_patterned(cfg: ModelConfig) -> bool:
    return bool(cfg.local_global_ratio or cfg.cross_attn_every)


def _period(cfg: ModelConfig) -> int:
    if cfg.cross_attn_every:
        return cfg.cross_attn_every
    if cfg.local_global_ratio:
        return cfg.local_global_ratio + 1
    return 1


def _pos_plan(cfg: ModelConfig, pos: int) -> dict:
    """Kind/window for position ``pos`` within a pattern period."""
    P = _period(cfg)
    kind = "self"
    window = cfg.window
    if cfg.cross_attn_every and pos == P - 1:
        kind = "cross"
    if cfg.local_global_ratio:
        window = 0 if pos == P - 1 else cfg.window
    return {"kind": kind, "window": window}


def layer_plan(cfg: ModelConfig) -> List[dict]:
    return [_pos_plan(cfg, i % _period(cfg)) for i in range(cfg.n_layers)]


def _split_blocks(cfg: ModelConfig):
    P = _period(cfg)
    n_full = cfg.n_layers // P
    rest = cfg.n_layers - n_full * P
    return P, n_full, rest


def _init_layer(key, cfg: ModelConfig, kind: str = "self"):
    d, hd = cfg.d_model, cfg.resolved_head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {"ln1": jnp.zeros((d,)), "ln2": jnp.zeros((d,))}
    if kind == "cross":
        p["attn"] = attention.init_cross_attn(
            k1, d, d, cfg.n_heads, cfg.n_kv_heads, hd)
    else:
        p["attn"] = attention.init_attn(
            k1, d, cfg.n_heads, cfg.n_kv_heads, hd, qk_norm=cfg.qk_norm)
    p["mlp"] = {
        "w_gate": common.dense_init(k2, (d, cfg.d_ff)),
        "w_up": common.dense_init(k3, (d, cfg.d_ff)),
        "w_down": common.dense_init(k4, (cfg.d_ff, d)),
    }
    return p


def init(key, cfg: ModelConfig):
    keys = jax.random.split(key, 8)
    params = {"embed": common.embed_init(keys[0], cfg.vocab, cfg.d_model),
              "ln_f": jnp.zeros((cfg.d_model,))}
    if not cfg.tie_embeddings:
        params["lm_head"] = common.dense_init(keys[1], (cfg.d_model, cfg.vocab))
    if cfg.d_media and cfg.d_media != cfg.d_model:
        params["media_proj"] = common.dense_init(keys[2], (cfg.d_media, cfg.d_model))
    if _is_patterned(cfg):
        P, n_full, rest = _split_blocks(cfg)
        if n_full:
            # list (len=P) of per-position stacks, each stacked over blocks
            pos_keys = jax.random.split(keys[3], P)
            params["blocks"] = [
                common.stack_layers(
                    pos_keys[p], n_full,
                    lambda k, p=p: _init_layer(k, cfg, _pos_plan(cfg, p)["kind"]))
                for p in range(P)]
        else:
            params["blocks"] = []
        params["rest"] = [
            _init_layer(k, cfg, _pos_plan(cfg, i)["kind"])
            for i, k in enumerate(jax.random.split(keys[4], rest))] if rest else []
    else:
        params["layers"] = common.stack_layers(
            keys[3], cfg.n_layers, lambda k: _init_layer(k, cfg))
    return params


def _mlp(p, x):
    h = common.swiglu(x, p["w_gate"], p["w_up"], p["w_down"])
    return shard_act(h, "batch", "seq", None)


def _embed(params, cfg: ModelConfig, tokens):
    with obs_trace.stage("model.embed"):
        h = params["embed"][tokens] * jnp.sqrt(float(cfg.d_model))
        return shard_act(h, "batch", "seq", None)


def _media_embed(params, cfg: ModelConfig, media):
    if "media_proj" in params:
        media = media @ params["media_proj"]
    return media


def _logits(params, cfg: ModelConfig, h):
    with obs_trace.stage("model.head"):
        h = common.rms_norm(h, params["ln_f"], cfg.norm_eps)
        w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        return shard_act(h @ w, "batch", None, "vocab")


# ---------------------------------------------------------------------------
# Layer application (mode: train | prefill | decode)
# ---------------------------------------------------------------------------

def _apply_layer(lp, cfg: ModelConfig, h, plan, *, positions=None, media=None,
                 mode="train", cache=None, pos=None, cache_len=0):
    with obs_trace.stage("model.mixer"):
        a, new_cache = _apply_mixer(lp, cfg, h, plan, positions=positions,
                                    media=media, mode=mode, cache=cache,
                                    pos=pos, cache_len=cache_len)
        h = h + a
    with obs_trace.stage("model.mlp"):
        h = h + _mlp(lp["mlp"], common.rms_norm(h, lp["ln2"], cfg.norm_eps))
    if mode == "train":
        new_cache = None        # never stack caches through the train scan
    return h, new_cache


def _apply_mixer(lp, cfg: ModelConfig, h, plan, *, positions, media, mode,
                 cache, pos, cache_len):
    """One layer's attention block, its pre-norm included: (a, cache)."""
    hd = cfg.resolved_head_dim
    hn = common.rms_norm(h, lp["ln1"], cfg.norm_eps)
    new_cache = cache
    if plan["kind"] == "cross":
        if mode == "decode":
            media_kv = cache
        else:
            media_kv = attention.cross_kv(lp["attn"], media, cfg.n_kv_heads, hd)
        a = attention.cross_attention(lp["attn"], hn, media_kv,
                                      n_heads=cfg.n_heads, head_dim=hd)
        new_cache = media_kv
    else:
        kw = dict(n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads, head_dim=hd,
                  theta=cfg.rope_theta, qk_norm=cfg.qk_norm,
                  norm_eps=cfg.norm_eps)
        w = plan["window"]
        if mode == "train":
            a = attention.self_attention(lp["attn"], hn, positions=positions,
                                         window=w, **kw)
        elif mode == "prefill":
            clen = min(cache_len, w + 1) if w else cache_len
            clen = max(clen, hn.shape[1])
            a, new_cache = attention.prefill_attention(
                lp["attn"], hn, positions=positions, cache_len=clen,
                window=w, **kw)
        else:  # decode
            if w:
                cap = cache.k.shape[1]
                kv_pos = jnp.arange(cap)
                valid = (kv_pos <= pos) | (pos >= cap)
                a, new_cache = attention.decode_attention(
                    lp["attn"], hn, cache, pos, write_pos=pos % cap,
                    kv_valid=valid, rope_pos=pos, **kw)
            else:
                a, new_cache = attention.decode_attention(
                    lp["attn"], hn, cache, pos, **kw)
    return a, new_cache


def _run_patterned(params, cfg: ModelConfig, h, *, positions=None, media=None,
                   mode="train", caches=None, pos=None, cache_len=0):
    """Scan over pattern blocks + unrolled remainder.

    ``caches``: {"blocks": [per-position stacked cache], "rest": [...]} or None.
    Returns (h, new_caches_with_same_structure)."""
    P, n_full, rest = _split_blocks(cfg)
    plans = [_pos_plan(cfg, p) for p in range(P)]

    new_caches = {"blocks": [None] * P, "rest": []}
    if n_full:
        def body(h, xs):
            lps, cs = xs
            new_cs = []
            for p in range(P):
                c = cs[p] if cs is not None else None
                h, nc = _apply_layer(lps[p], cfg, h, plans[p],
                                     positions=positions, media=media,
                                     mode=mode, cache=c, pos=pos,
                                     cache_len=cache_len)
                new_cs.append(nc)
            return h, tuple(new_cs)
        if mode == "train" and cfg.remat:
            body = jax.checkpoint(body)
        xs = (tuple(params["blocks"]),
              tuple(caches["blocks"]) if caches else None)
        h, blk_caches = jax.lax.scan(body, h, xs)
        new_caches["blocks"] = list(blk_caches)
    for i, lp in enumerate(params["rest"]):
        c = caches["rest"][i] if caches else None
        h, nc = _apply_layer(lp, cfg, h, plans[(n_full * P + i) % P] if P else plans[0],
                             positions=positions, media=media, mode=mode,
                             cache=c, pos=pos, cache_len=cache_len)
        new_caches["rest"].append(nc)
    return h, new_caches


# ---------------------------------------------------------------------------
# Full-sequence forward (training / scoring)
# ---------------------------------------------------------------------------

def forward(params, cfg: ModelConfig, tokens, media: Optional[jnp.ndarray] = None):
    B, S = tokens.shape
    h = _embed(params, cfg, tokens)
    positions = jnp.arange(S)
    if _is_patterned(cfg):
        m = _media_embed(params, cfg, media) if media is not None else None
        h, _ = _run_patterned(params, cfg, h, positions=positions, media=m,
                              mode="train")
        return _logits(params, cfg, h)

    kw = dict(n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads,
              head_dim=cfg.resolved_head_dim, positions=positions,
              theta=cfg.rope_theta, qk_norm=cfg.qk_norm, norm_eps=cfg.norm_eps)

    def body(h, lp):
        with obs_trace.stage("model.mixer"):
            h = h + attention.self_attention(
                lp["attn"], common.rms_norm(h, lp["ln1"], cfg.norm_eps),
                window=cfg.window, **kw)
        with obs_trace.stage("model.mlp"):
            h = h + _mlp(lp["mlp"],
                         common.rms_norm(h, lp["ln2"], cfg.norm_eps))
            # residual stream at the layer boundary: with seq -> 'model'
            # (sequence parallelism, §Perf A) the saved activations shard
            # 16-way
            h = shard_act(h, "batch", "seq", None)
        return h, None
    if cfg.remat:
        body = jax.checkpoint(body)
    h, _ = jax.lax.scan(body, h, params["layers"])
    return _logits(params, cfg, h)


# ---------------------------------------------------------------------------
# Serving: prefill + one-token decode
# ---------------------------------------------------------------------------

class ServeCache(NamedTuple):
    layers: object          # stacked KVCache (scan) or patterned dict
    media_kv: object        # unused for patterned (cross kv lives in layers)


def prefill(params, cfg: ModelConfig, tokens, cache_len: int,
            media: Optional[jnp.ndarray] = None):
    B, S = tokens.shape
    h = _embed(params, cfg, tokens)
    positions = jnp.arange(S)
    if _is_patterned(cfg):
        m = _media_embed(params, cfg, media) if media is not None else None
        h, caches = _run_patterned(params, cfg, h, positions=positions,
                                   media=m, mode="prefill",
                                   cache_len=cache_len)
        return _logits(params, cfg, h[:, -1:]), ServeCache(caches, None)

    hd = cfg.resolved_head_dim
    kw = dict(n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads, head_dim=hd,
              positions=positions, theta=cfg.rope_theta,
              qk_norm=cfg.qk_norm, norm_eps=cfg.norm_eps)

    def body(h, lp):
        with obs_trace.stage("model.mixer"):
            a, kv = attention.prefill_attention(
                lp["attn"], common.rms_norm(h, lp["ln1"], cfg.norm_eps),
                cache_len=max(cache_len, S), window=cfg.window, **kw)
            h = h + a
        with obs_trace.stage("model.mlp"):
            h = h + _mlp(lp["mlp"],
                         common.rms_norm(h, lp["ln2"], cfg.norm_eps))
        return h, kv
    h, caches = jax.lax.scan(body, h, params["layers"])
    return _logits(params, cfg, h[:, -1:]), ServeCache(caches, None)


def _empty_kv(cfg, batch, clen):
    hd = cfg.resolved_head_dim
    dt = jnp.dtype(cfg.param_dtype)
    shape = (batch, clen, cfg.n_kv_heads, hd)
    return attention.KVCache(jnp.zeros(shape, dt), jnp.zeros(shape, dt))


def init_decode_cache(cfg: ModelConfig, batch: int, cache_len: int,
                      media: Optional[jnp.ndarray] = None, params=None):
    """Empty caches for pure-decode lowering (decode_32k / long_500k)."""
    hd = cfg.resolved_head_dim
    if not _is_patterned(cfg):
        one = _empty_kv(cfg, batch, cache_len)
        stacked = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x, (cfg.n_layers,) + x.shape), one)
        return ServeCache(stacked, None)

    P, n_full, rest = _split_blocks(cfg)
    plans = [_pos_plan(cfg, p) for p in range(P)]

    def pos_cache(plan, stacked_n=0):
        if plan["kind"] == "cross":
            M = cfg.n_media_tokens or 8
            dt = jnp.dtype(cfg.param_dtype)
            kvshape = (batch, M, cfg.n_kv_heads, hd)
            c = attention.KVCache(jnp.zeros(kvshape, dt),
                                  jnp.zeros(kvshape, dt))
        else:
            w = plan["window"]
            clen = min(cache_len, w + 1) if w else cache_len
            c = _empty_kv(cfg, batch, clen)
        if stacked_n:
            c = jax.tree_util.tree_map(
                lambda x: jnp.broadcast_to(x, (stacked_n,) + x.shape), c)
        return c

    caches = {"blocks": [pos_cache(plans[p], n_full) for p in range(P)]
              if n_full else [],
              "rest": [pos_cache(plans[(n_full * P + i) % P])
                       for i in range(rest)]}
    if media is not None and params is not None:
        # fill cross caches with real media kv per layer
        m = _media_embed(params, cfg, media)
        if n_full:
            for p in range(P):
                if plans[p]["kind"] == "cross":
                    kv = jax.vmap(
                        lambda lp: attention.cross_kv(lp["attn"], m,
                                                      cfg.n_kv_heads, hd)
                    )(params["blocks"][p])
                    caches["blocks"][p] = kv
        for i in range(rest):
            if plans[(n_full * P + i) % P]["kind"] == "cross":
                caches["rest"][i] = attention.cross_kv(
                    params["rest"][i]["attn"], m, cfg.n_kv_heads, hd)
    return ServeCache(caches, None)


def decode_step(params, cfg: ModelConfig, token, cache: ServeCache, pos):
    """token [B,1] int32; pos scalar int32.  Returns (logits [B,1,V], cache)."""
    h = _embed(params, cfg, token)
    if _is_patterned(cfg):
        h, new_caches = _run_patterned(params, cfg, h, mode="decode",
                                       caches=cache.layers, pos=pos)
        return _logits(params, cfg, h), ServeCache(new_caches, None)

    hd = cfg.resolved_head_dim
    kw = dict(n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads, head_dim=hd,
              theta=cfg.rope_theta, qk_norm=cfg.qk_norm, norm_eps=cfg.norm_eps)

    def body(h, xs):
        lp, c = xs
        with obs_trace.stage("model.mixer"):
            a, kvn = attention.decode_attention(
                lp["attn"], common.rms_norm(h, lp["ln1"], cfg.norm_eps),
                c, pos, window=cfg.window, **kw)
            h = h + a
        with obs_trace.stage("model.mlp"):
            h = h + _mlp(lp["mlp"],
                         common.rms_norm(h, lp["ln2"], cfg.norm_eps))
        return h, kvn
    h, new_caches = jax.lax.scan(body, h, (params["layers"], cache.layers))
    return _logits(params, cfg, h), ServeCache(new_caches, None)
