"""Faults planted in the timed path underneath a whole run make
``correct`` false: a round that returns its state unchanged, and an
aggregate that leaves out half of the participants and averages over the
rest.  (One chip: no exchange between chips to leave out.)"""
from __future__ import annotations

import jax
from runs import run_cell

from repro.comm import flat
from repro.engine import rounds


def test_state_left_unchanged(small_bench, capsys, monkeypatch):
    real = rounds.round_program

    def frozen(loss_pair, cfg):
        step = real(loss_pair, cfg)

        def run(state, batch):
            _, mets = step(state, batch)
            return jax.tree_util.tree_map(lambda x: x, state), mets
        return jax.jit(run)

    monkeypatch.setattr(rounds, "round_program", frozen)
    out = run_cell(small_bench, capsys, "small-dense.quant")
    assert out["correct"] is False
    assert out["checks"]["step1_gap"]["value"] > 0.5


def test_half_of_the_participants_left_out(small_bench, capsys, monkeypatch):
    real = flat.FlatTransport.transmit_gathered

    def half(self, e, deltas, idx, mask, m, like=None, key=None):
        keep = max(1, m // 2)
        drop = jax.numpy.zeros_like(mask).at[idx[keep:]].set(1.0)
        return real(self, e, deltas, idx, mask * (1.0 - drop), keep,
                    like=like, key=key)

    monkeypatch.setattr(flat.FlatTransport, "transmit_gathered", half)
    out = run_cell(small_bench, capsys, "small-dense.quant")
    assert out["correct"] is False
