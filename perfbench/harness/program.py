"""The system under test, built as ``launch/train.py`` builds it, and driven
round after round through the jitted, state-donating
``repro.engine.rounds.round_program``, compiled once for the cell's shapes:
the first rounds and the window call that one compiled program.

The weights come from the configuration's reference ``init`` (one jitted
call on the device, float32, from the seed) in the layout of the model's
own parameters; the program receives only them and the traffic's batches.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from harness import reference, traffic


def model_config(model: dict):
    from repro.configs.base import ModelConfig, SSMConfig
    kw = dict(model)
    if kw.get("ssm") is not None:
        kw["ssm"] = SSMConfig(**kw["ssm"])
    return ModelConfig(**kw)


def fed_config(federation: dict, mix: dict):
    from repro.configs.base import CompressorConfig, FedConfig, SwitchConfig
    up = mix["uplink"]
    return FedConfig(
        n_clients=mix["clients"], m=mix["participating"],
        local_steps=mix["local_steps"], lr=federation["lr"],
        switch=SwitchConfig(**federation["switch"]),
        uplink=CompressorConfig(kind=up["kind"], ratio=up.get("ratio", 0.1),
                                bits=up.get("bits", 8),
                                block=up.get("block", 1024)),
        downlink=CompressorConfig(kind=federation["downlink"]),
        comm=federation["comm"], strategy=federation["strategy"],
        participation=federation["participation"],
        full_eval=federation["full_eval"],
        track_wbar=federation["track_wbar"])


class Program:
    """One cell's round program, weights maker and batch maker."""

    def __init__(self, config: dict, mix: dict, ref_mod):
        from repro.core import fedsgm
        from repro.engine import rounds
        from repro.models import build
        from repro.tasks import lm
        self.fedsgm, self.lm = fedsgm, lm
        self.ref_mod = ref_mod
        self.federation = config["federation"]
        self.model = config["model"]
        self.cfg = model_config(self.model)
        self.fed = fed_config(config["federation"], mix)
        self.mix = mix
        fns = build(self.cfg)
        loss_pair = lm.make_loss_pair(fns.forward, self.cfg,
                                      budget=config["federation"]["budget"])
        self.round_program = rounds.round_program(loss_pair, self.fed)
        self.step = None        # the compiled round, made by state()
        self.init = jax.jit(lambda k: ref_mod.init(k, self.model))
        want = jax.eval_shape(lambda: fns.init(jax.random.PRNGKey(0),
                                               self.cfg))
        have = jax.eval_shape(self.init, jax.random.PRNGKey(0))
        if jax.tree_util.tree_structure(want) != \
                jax.tree_util.tree_structure(have) or any(
                    a.shape != b.shape or a.dtype != b.dtype for a, b in zip(
                        jax.tree_util.tree_leaves(want),
                        jax.tree_util.tree_leaves(have))):
            raise SystemExit(f"the reference's weights for {config['name']} "
                             "do not match the model's parameter layout")
        self.names = reference.leaf_names(have)
        self.leaf_shapes = [tuple(a.shape)
                            for a in jax.tree_util.tree_leaves(have)]
        self.gen = traffic.round_batch_fn(mix, self.cfg.vocab)
        self.norms = jax.jit(reference.leaf_norms)
        self.tokens_per_round = (mix["participating"] * mix["local_steps"]
                                 * mix["batch"] * mix["seq"])

    def state(self, seed: int):
        """(state, keys) of a fresh run."""
        k_w, k_s, k_t = traffic.keys(seed)
        # the round program donates the state, its key with it: the state
        # takes a copy, and k_s stays for the reference
        state = self.fedsgm.init_state(self.init(k_w), self.fed,
                                       key=jnp.copy(k_s))
        if self.step is None:
            self.step = self.round_program.lower(
                state, self.batch(k_t, 0)).compile()
        return state, (k_w, k_s, k_t)

    def batch(self, k_t, t: int):
        toks, mask = self.gen(k_t, jnp.int32(t))
        return self.lm.LMBatch(tokens=toks, minority_mask=mask)

    def first_rounds(self, state, keys, rounds: int):
        """Drive ``rounds`` rounds through the window's own step and feed,
        reading what the comparison needs.  Returns (state, Readings)."""
        k_w, _, k_t = keys
        mets, step1 = [], None
        for t in range(rounds):
            state, met = self.step(state, self.batch(k_t, t))
            mets.append(met)
            if t == 0:
                step1 = self.norms(state.w, self.init(k_w))
        change = self.norms(state.w, self.init(k_w))
        got = jax.device_get(([(m.f, m.g_hat) for m in mets], step1, change,
                              (mets[0].up_bytes, mets[0].down_bytes)))
        self.wire_bytes = tuple(int(b) for b in got[3])
        fg = np.asarray(got[0], dtype=np.float64)
        return state, reference.Readings(fg[:, 0], fg[:, 1],
                                         np.asarray(got[1]),
                                         np.asarray(got[2]))
