"""The control: the plain reference computed in bfloat16, put in the
program's place, comes out as not correct (the configuration states
float32)."""
from __future__ import annotations

import jax.numpy as jnp
from runs import run_cell

from harness import program, reference


def test_bfloat16_control_is_not_correct(small_bench, capsys, monkeypatch):
    real = program.Program.first_rounds

    def control_rounds(self, state, keys, rounds):
        state, _ = real(self, state, keys, rounds)
        ctl = reference.Reference(self.ref_mod, self.model,
                                  self.federation, self.mix,
                                  dtype=jnp.bfloat16)
        k_w, k_s, k_t = keys
        got = ctl.run(lambda: self.init(k_w), k_s,
                      lambda t: tuple(self.batch(k_t, t))[:2], rounds)
        return state, got

    monkeypatch.setattr(program.Program, "first_rounds", control_rounds)
    out = run_cell(small_bench, capsys, "small-dense.quant")
    assert out["correct"] is False
    over = [k for k, c in out["checks"].items() if c["value"] > c["limit"]]
    assert over
