"""Language-model task adapters for FedSGM.

The NP-classification structure generalized to LM training: the *majority*
objective f is next-token CE on ordinary tokens; the *constraint* g is CE on
the minority slice (rare-token domain) minus a budget -- i.e. "keep minority
perplexity below budget while minimizing majority loss".  For MoE models the
constraint can instead target router load balance (DESIGN.md §5).
"""
from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import common
from repro.obs import trace as obs_trace


class LMBatch(NamedTuple):
    tokens: jnp.ndarray          # [B, S] int32
    minority_mask: jnp.ndarray   # [B, S] float32 (1 = constraint slice)
    media: object = None         # [B, M, d_media] stub embeddings (vlm/audio)


def make_fleet(key, fed_cfg, pool: int, seq_len: int, vocab: int,
               hetero: float = 0.5):
    """Client population for LM training (repro.fleet): each client holds a
    pool of ``pool`` token sequences from its own Zipf-shifted stream
    (quantity ``hetero`` spreads the zipf exponent across clients), and the
    fleet's in-jit provisioning draws ``fed_cfg.fleet.batch_size`` fresh
    sequences per round -- replacing the host-side per-round regeneration
    so the whole multi-round driver (engine.rounds.drive) stays jitted."""
    from repro.data import synthetic
    from repro.fleet import provision
    toks, mask = synthetic.client_token_batches(
        key, fed_cfg.n_clients, pool, seq_len, vocab, hetero=hetero)
    return provision.from_stacked(LMBatch(tokens=toks, minority_mask=mask))


def make_loss_pair(model_forward, cfg: ModelConfig, budget: float = 0.0,
                   aux_constraint: bool = False, mtp_weight: float = 0.3):
    """Return loss_pair(params, batch) -> (f, g) for fedsgm.round_step.

    aux_constraint=True uses the model's auxiliary scalar (MoE load
    imbalance) as g; the forward must then return (logits, aux[, mtp_logits]).
    """

    def loss_pair(params, batch: LMBatch):
        kwargs = {}
        if batch.media is not None:
            kwargs["media"] = batch.media
        # forward over the FULL sequence (length stays mesh-divisible for
        # sequence sharding, §Perf A4'); the last position carries no target
        out = model_forward(params, cfg, batch.tokens, **kwargs)
        aux, mtp_logits = None, None
        if isinstance(out, tuple):
            if len(out) == 3:
                out, aux, mtp_logits = out
            else:
                out, aux = out
        with obs_trace.stage("model.loss"):
            out = out[:, :-1]
            targets = batch.tokens[:, 1:]
            mmask = batch.minority_mask[:, 1:]
            f = common.cross_entropy(out, targets, mask=1.0 - mmask)
            if mtp_logits is not None:
                # MTP: logits at t predict token t+2
                f = f + mtp_weight * common.cross_entropy(
                    mtp_logits[:, :-1], targets[:, 1:])
            if aux_constraint and aux is not None:
                g = aux - budget
            else:
                g = common.cross_entropy(out, targets, mask=mmask) - budget
            return f, g

    return loss_pair
