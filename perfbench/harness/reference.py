"""Plain reference of the FedSGM round, and the comparison that decides
``correct``.

One round, written out client by client on parameter trees with nothing of
the system under test: sample the m participants (uniform without
replacement from the round key), evaluate ``(f, g)`` on the evaluated
clients, the soft switch ``sigma = clip(1 + beta (g_hat - eps), 0, 1)``, E
local SGD steps on ``(1 - sigma) f + sigma g`` per participant, the EF14
uplink ``v_j = C(e_j + delta_j)``, ``e_j <- e_j + delta_j - v_j`` with C the
per-block compressor of each leaf's last axis, the participating mean
``v_bar``, and the server step ``w <- w - lr v_bar`` (no downlink
compression).  The model's loss comes from the configuration's own
reference module.

The reference runs in float32 under ``highest`` matmul precision.  The
control is the same code computed in bfloat16: weights, residuals, the
model, the local steps, the aggregate and the server step.
"""
from __future__ import annotations

import contextlib
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

tree_map = jax.tree_util.tree_map


def choose_block(d: int, pref: int) -> int:
    """Largest divisor of ``d`` not above ``pref``."""
    b = max(1, min(pref, d))
    while d % b:
        b -= 1
    return b


def _blocks(x, pref):
    d = x.shape[-1] if x.ndim else 1
    b = choose_block(d, pref)
    return x.reshape(-1, b), b


def quantize(x, bits: int, pref: int):
    """Per-block max-abs symmetric ``bits``-bit rounding (half to even)."""
    blk, _ = _blocks(x, pref)
    levels = float(2 ** (bits - 1) - 1)
    scale = jnp.max(jnp.abs(blk), axis=-1, keepdims=True)
    safe = jnp.where(scale > 0, scale, 1.0)
    q = jnp.where(scale > 0, jnp.round(blk / safe * levels) / levels * safe,
                  0.0)
    return q.reshape(x.shape)


def topk(x, ratio: float, pref: int):
    """Per-block magnitude top-k, k = round(block * ratio)."""
    blk, b = _blocks(x, pref)
    k = max(1, min(b, int(round(b * ratio))))
    if k >= b:
        return x
    _, idx = jax.lax.top_k(jnp.abs(blk), k)
    rows = jnp.arange(blk.shape[0])[:, None]
    out = jnp.zeros_like(blk).at[rows, idx].set(
        jnp.take_along_axis(blk, idx, axis=-1))
    return out.reshape(x.shape)


def compressor(uplink: dict):
    kind, pref = uplink["kind"], uplink.get("block", 1024)
    if kind == "quant":
        return lambda x: quantize(x, uplink["bits"], pref)
    if kind == "topk":
        return lambda x: topk(x, uplink["ratio"], pref)
    raise ValueError(f"no reference compressor for uplink {kind!r}")


def leaf_norms(a, b):
    """[leaves] float32: ||a_i - b_i|| for each leaf (tree order)."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32) - y.astype(jnp.float32))))
        for x, y in zip(jax.tree_util.tree_leaves(a),
                        jax.tree_util.tree_leaves(b))])


def leaf_names(tree) -> list:
    return ["/".join(str(getattr(k, "key", k)) for k in path)
            for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def participants(key, n: int, m: int) -> np.ndarray:
    """Sorted participant ids of a round from its sampling key."""
    if m >= n:
        return np.arange(n)
    perm = np.asarray(jax.random.permutation(key, n))
    return np.flatnonzero(perm < m)


class Readings(NamedTuple):
    """What one run of the rounds gives for the comparison."""
    f: np.ndarray           # [rounds] participating mean objective
    g: np.ndarray           # [rounds] participating mean constraint
    step1: np.ndarray       # [leaves] ||w_1 - w_0||
    change: np.ndarray      # [leaves] ||w_R - w_0||


class Reference:
    """The plain rounds of one cell.  ``dtype`` is the type everything is
    held and computed in (float32: the reference; bfloat16: the control).
    ``fault="half"`` aggregates only the first half of the participants,
    averaging over them (a planted fault, for the test of the
    comparison)."""

    def __init__(self, ref_mod, model: dict, federation: dict,
                 traffic: dict, dtype=jnp.float32,
                 fault: Optional[str] = None):
        self.n, self.m = traffic["clients"], traffic["participating"]
        self.E = traffic["local_steps"]
        self.full_eval = federation["full_eval"]
        self.fault = fault
        sw = federation["switch"]
        lr, budget = federation["lr"], federation["budget"]
        comp = compressor(traffic["uplink"])
        highest = dtype == jnp.float32

        def loss(w, tok, mm):
            return ref_mod.loss_pair(w, tok, mm, model, budget, dtype)

        def objective(w, tok, mm, sigma):
            f, g = loss(w, tok, mm)
            return (1.0 - sigma) * f + sigma * g

        def client(w, e, tok, mm, sigma):
            grad = jax.grad(objective)

            def step(wj, _):
                return tree_map(lambda a, g: a - lr * g, wj,
                                grad(wj, tok, mm, sigma)), None
            w_e, _ = jax.lax.scan(step, w, None, length=self.E)
            buf = tree_map(lambda ei, a, b: ei + (a - b) / lr, e, w, w_e)
            v = tree_map(comp, buf)
            return v, tree_map(jnp.subtract, buf, v)

        self.precision = (lambda: jax.default_matmul_precision("highest")) \
            if highest else contextlib.nullcontext
        # the CPU ignores donation; elsewhere it keeps one copy of each tree
        donate = jax.default_backend() != "cpu"
        self.loss = jax.jit(loss)
        self.client = jax.jit(client, donate_argnums=(1,) if donate else ())
        self.add = jax.jit(lambda acc, v: tree_map(jnp.add, acc, v),
                           donate_argnums=(0,) if donate else ())
        self.server = jax.jit(
            lambda w, acc, cnt: tree_map(lambda a, s: a - lr * s / cnt,
                                         w, acc),
            donate_argnums=(0, 1) if donate else ())
        self.sw = sw
        self.dtype = dtype
        self.norms = jax.jit(leaf_norms)
        self.zeros = jax.jit(lambda t: tree_map(jnp.zeros_like, t))

    def _cast(self, init):
        if self.dtype == jnp.float32:
            return init
        return lambda: tree_map(lambda a: a.astype(self.dtype), init())

    def sigma(self, g_hat: float) -> float:
        if self.sw["mode"] == "soft":
            return float(np.clip(1.0 + self.sw["beta"]
                                 * (g_hat - self.sw["eps"]), 0.0, 1.0))
        return float(g_hat > self.sw["eps"])

    def run(self, init, k_state, batches, rounds: int) -> Readings:
        """``rounds`` rounds from ``init()`` (a fresh copy of w_0 each
        call), the federation key ``k_state`` and ``batches(t) -> (tokens
        [n, B, S], minority [n, B, S])``."""
        with self.precision():
            return self._run(init, k_state, batches, rounds)

    def _run(self, init, k_state, batches, rounds):
        init = self._cast(init)
        w = init()
        resid: dict = {}
        fs, gs = [], []
        step1 = None
        key = k_state
        for t in range(rounds):
            key, k_part, _, _ = jax.random.split(key, 4)
            idx = participants(k_part, self.n, self.m)
            tok, mm = batches(t)
            ev = range(self.n) if self.full_eval else idx
            fg = {j: self.loss(w, tok[j], mm[j]) for j in ev}
            f_hat = float(sum(float(fg[j][0]) for j in idx)) / self.m
            g_hat = float(sum(float(fg[j][1]) for j in idx)) / self.m
            fs.append(f_hat)
            gs.append(g_hat)
            sigma = self.sigma(g_hat)
            kept = idx[:max(1, len(idx) // 2)] if self.fault == "half" \
                else idx
            acc = None
            for j in idx:
                e = resid.pop(j) if j in resid else self.zeros(w)
                v, resid[j] = self.client(w, e, tok[j], mm[j], sigma)
                if j in kept:
                    acc = v if acc is None else self.add(acc, v)
                del v
            w = self.server(w, acc, float(len(kept)))
            if t == 0:
                w0 = init()
                step1 = np.asarray(self.norms(w, w0))
                del w0
        w0 = init()
        change = np.asarray(self.norms(w, w0))
        return Readings(np.asarray(fs), np.asarray(gs), step1, change)


def compare(prog: Readings, ref: Readings, names: list) -> dict:
    """The numbers that decide ``correct``, each with where it is worst.

    * ``loss_gap``: largest |program - reference| / |reference| over the
      rounds' f and g_hat,
    * ``step1_gap`` / ``change_gap``: by the worst leaf, the gap between
      the program's and the reference's norm of the first update
      (``w_1 - w_0``, the aggregated direction times lr) and of the change
      over all compared rounds, over the larger of the reference leaf's
      norm and the median leaf's.  Leaves whose reference first update is
      under a thousandth of the median leaf's are left out.
    """
    loss = np.concatenate([np.abs(prog.f - ref.f) / np.abs(ref.f),
                           np.abs(prog.g - ref.g) / np.abs(ref.g)])
    kept = ref.step1 >= 1e-3 * np.median(ref.step1)
    out = {"loss_gap": (float(np.max(loss)), int(np.argmax(loss)))}
    for key, p, r in (("step1_gap", prog.step1, ref.step1),
                      ("change_gap", prog.change, ref.change)):
        gap = np.abs(p - r) / np.maximum(r, np.median(r[kept]))
        gap = np.where(kept, gap, 0.0)
        i = int(np.argmax(gap))
        out[key] = (float(gap[i]), names[i])
    return out
