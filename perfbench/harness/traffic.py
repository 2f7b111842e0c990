"""The one traffic generator: per-client token batches of a round, made on
the device from the seed and the round number.

A copy of ``repro.data.synthetic.client_token_batches`` (Zipf tokens, a
copied induction span, and a rare-token minority tail that is the
constraint slice), so that the yardstick cannot move with the program.
Every parameter comes from the traffic file.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def token_stream(key, batch: int, seq: int, vocab: int,
                 minority_frac: float, zipf_a: float):
    k1, k2, _ = jax.random.split(key, 3)
    ranks = jnp.arange(1, vocab + 1, dtype=jnp.float32)
    probs = ranks ** (-zipf_a)
    probs = probs / probs.sum()
    toks = jax.random.choice(k1, vocab, shape=(batch, seq), p=probs)
    span = max(1, seq // 8)
    toks = toks.at[:, span:2 * span].set(toks[:, :span])
    m = max(1, int(seq * minority_frac))
    rare = jax.random.randint(k2, (batch, m), vocab // 2, vocab)
    toks = toks.at[:, -m:].set(rare)
    mask = jnp.zeros((batch, seq), jnp.float32).at[:, -m:].set(1.0)
    return toks, mask


def client_batches(key, traffic: dict, vocab: int):
    """([n, batch, seq] int32 tokens, [n, batch, seq] f32 minority mask):
    client ``j`` draws from a Zipf law whose exponent is spread by
    ``hetero`` across the clients."""
    n = traffic["clients"]
    keys = jax.random.split(key, n)
    zipfs = traffic["zipf_a"] + traffic["hetero"] * np.linspace(-0.3, 0.3, n)
    toks, masks = zip(*[
        token_stream(keys[j], traffic["batch"], traffic["seq"], vocab,
                     traffic["minority_frac"], float(zipfs[j]))
        for j in range(n)])
    return jnp.stack(toks), jnp.stack(masks)


def seed_key(seed: int):
    """A PRNG key from a seed of any size (``PRNGKey`` alone keeps only the
    low 32 bits)."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


def keys(seed: int):
    """(weights key, federation-state key, traffic key) of a run."""
    return tuple(jax.random.split(seed_key(seed), 3))


def round_batch_fn(traffic: dict, vocab: int):
    """Jitted ``(traffic_key, t) -> (tokens, mask)`` of round ``t``; its
    device ops carry ``perfbench_traffic`` in their metadata."""
    def perfbench_traffic(k, t):
        return client_batches(jax.random.fold_in(k, t), traffic, vocab)
    return jax.jit(perfbench_traffic)
