"""Population scale-out benchmark (ISSUE 6): O(m·d) EF slot-store memory
and round time across population sizes, and hierarchical two-tier payload
aggregation vs the flat single-tier reduce.

Three record families, seeding BENCH_scale.json:

* ``memory`` -- resident bytes of the uplink EF state: the dense [n, d]
  ``e_up`` grows linearly in the population while the slot store
  (``repro.scale.slots``, cap = 2m) holds [cap, d] + a 4-byte-per-client
  index, for n in {512, 8192, 65536} at m = 64.  Machine-independent
  (array arithmetic, not RSS).
* ``rounds`` -- engine round wall-time in slot mode at each n (the dense
  path is SKIPPED past ``DENSE_LIMIT`` resident bytes -- at n = 65536 the
  dense residual alone would hold > 1 GB; slot mode runs it in < 3 MB of
  EF state).
* ``twotier`` -- ``FlatTransport.reduce`` latency sweeping the cohort
  count k in {1, 2, 4, 8} on the same payload stack (select scatter-add
  and quant unpack-multiply-add), with the max deviation vs the flat
  k = 1 reduce recorded per k.
* ``sharded`` (``--sharded``, separate subprocess) -- ``shard.sharded_take``
  latency under a forced 4-host-device mesh vs the meshless take, with the
  gathered rows checked exact; a parity/latency probe of the client-axis
  sharding on hosts without accelerators.

``--smoke`` is the CI guard (job ``scale-smoke``):

* slot parity: cap >= n trajectories must be bit-identical to the dense
  gather engine for select (packed), quant (packed) and the dense wire,
* two-tier exactness: for *integer-valued* f32 select payloads with 0/1
  weights and power-of-two row counts every cohort split is an exact sum,
  so the two-tier select reduce must be BIT-equal to flat for every k;
  real-float quant payloads are a reordered sum -- pinned allclose,
* memory: the slot store at n = 65536 must hold >= 16x less than the
  dense residual (array arithmetic -- machine-independent),
* regression: the slot-mode round (cap >= n) vs the same-run dense gather
  round; a BENCH_scale.json baseline can excuse a borderline reading but
  a cross-machine absolute number alone never fails the build.

    PYTHONPATH=src python -m benchmarks.scale_bench [--smoke] [--out F]
"""
from __future__ import annotations

import argparse
import json
import sys

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, timed
from repro.comm import flat, transports
from repro.configs.base import (CompressorConfig, FedConfig, ScaleConfig,
                                SwitchConfig)
from repro.engine import rounds
from repro.scale import slots

tree_map = jax.tree_util.tree_map

# Population sweep: m fixed, n spans 3 decades.  The model is sized so the
# dense [n, d] residual crosses a real memory cliff inside the sweep.
NS = (512, 8192, 65536)
M = 64
CAP = 128                       # slot-store capacity (2m: re-sample locality)
DENSE_LIMIT = 512 * 1024 * 1024  # skip dense-mode runs past this e_up size

D, H, PER = 64, 64, 8


def _init_params(key):
    k1, k2 = jax.random.split(key)
    return {"W1": 0.1 * jax.random.normal(k1, (D, H)),
            "b1": jnp.zeros((H,)),
            "W2": 0.1 * jax.random.normal(k2, (H,)),
            "b2": jnp.zeros(())}


def _loss_pair(params, batch):
    x, y = batch
    z = jnp.tanh(x @ params["W1"] + params["b1"])
    logits = z @ params["W2"] + params["b2"]
    per_ex = jax.nn.softplus(logits) - logits * y
    m0 = (y == 0).astype(jnp.float32)
    m1 = (y == 1).astype(jnp.float32)
    f = jnp.sum(per_ex * m0) / jnp.maximum(jnp.sum(m0), 1.0)
    g = jnp.sum(per_ex * m1) / jnp.maximum(jnp.sum(m1), 1.0)
    return f, g


def _batches(key, n):
    kx, ky = jax.random.split(key)
    x = jax.random.normal(kx, (n, PER, D))
    y = (jax.random.uniform(ky, (n, PER)) < 0.3).astype(jnp.float32)
    return (x, y)


def _cfg(n, m, comm="packed", E=4, cap=0, cohorts=1,
         uplink=None):
    return FedConfig(
        n_clients=n, m=m, local_steps=E, lr=0.05,
        switch=SwitchConfig(mode="soft", eps=0.35, beta=6.0),
        uplink=uplink or CompressorConfig(kind="topk", ratio=0.25, block=32),
        downlink=CompressorConfig(kind="none"),
        comm=comm, participation="gather", full_eval=False,
        track_wbar=False,
        scale=ScaleConfig(ef_slots=cap, cohorts=cohorts))


def _dense_ef_bytes(n: int, d: int) -> int:
    return n * d * 4


# ---------------------------------------------------------------------------
# Memory records (machine-independent: array arithmetic, not RSS)
# ---------------------------------------------------------------------------

def memory_records(ns=NS, m=M, cap=CAP):
    params = _init_params(jax.random.PRNGKey(0))
    spec = flat.spec_of(params)
    records = []
    for n in ns:
        store = slots.init(n, cap, spec.d, spec.dtype)
        slot_b = slots.resident_bytes(store)
        dense_b = _dense_ef_bytes(n, spec.d)
        rec = {"n": n, "m": m, "cap": cap, "d": spec.d,
               "ef_dense_bytes": dense_b, "ef_slot_bytes": slot_b,
               "saving": round(dense_b / slot_b, 1),
               "dense_feasible": dense_b <= DENSE_LIMIT}
        records.append(rec)
        emit(f"scale_memory_n{n}", 0.0,
             f"dense={dense_b};slots={slot_b};saving={rec['saving']}x")
    return records


# ---------------------------------------------------------------------------
# Round-time records
# ---------------------------------------------------------------------------

def _time_round(cfg, params, batches, iters=2, warmup=1):
    state = rounds.init_state(params, cfg)
    step = jax.jit(lambda s, b: rounds.round_step(s, b, _loss_pair, cfg))
    us, _ = timed(step, state, batches, warmup=warmup, iters=iters)
    return us


def round_records(ns=NS, m=M, cap=CAP, E=4, iters=2):
    key = jax.random.PRNGKey(0)
    params = _init_params(key)
    spec = flat.spec_of(params)
    records = []
    for n in ns:
        batches = _batches(jax.random.fold_in(key, n), n)
        us_slot = _time_round(_cfg(n, m, E=E, cap=cap), params, batches,
                              iters=iters)
        dense_b = _dense_ef_bytes(n, spec.d)
        us_dense = None
        if dense_b <= DENSE_LIMIT:
            us_dense = _time_round(_cfg(n, m, E=E), params, batches,
                                   iters=iters)
        rec = {"n": n, "m": m, "cap": cap, "local_steps": E,
               "us_slot_round": round(us_slot, 1),
               "rounds_per_sec_slot": round(1e6 / us_slot, 2),
               "us_dense_round": (round(us_dense, 1)
                                  if us_dense is not None else None),
               "dense_skipped": us_dense is None}
        records.append(rec)
        emit(f"scale_round_n{n}", us_slot,
             f"rps_slot={rec['rounds_per_sec_slot']};dense="
             f"{'skipped' if us_dense is None else round(us_dense, 1)}")
    return records


# ---------------------------------------------------------------------------
# Two-tier aggregation records
# ---------------------------------------------------------------------------

def _agg_params(key):
    """Model-scale tree (d ~ 132k): aggregation cost is about the payload
    stream."""
    return {"W1": 0.1 * jax.random.normal(key, (256, 512)),
            "b1": jnp.zeros((512,)),
            "W2": 0.1 * jax.random.normal(jax.random.fold_in(key, 1),
                                          (512,)),
            "b2": jnp.zeros(())}


def twotier_records(n=256, ks=(1, 2, 4, 8), iters=3):
    key = jax.random.PRNGKey(0)
    params = _agg_params(key)
    spec = flat.spec_of(params)
    deltas = jax.random.normal(jax.random.fold_in(key, 2), (n, spec.d))
    weights = (jax.random.uniform(jax.random.fold_in(key, 3), (n,))
               < 0.5).astype(jnp.float32)
    m = float(jnp.sum(weights))
    records = []
    for name, ccfg in (
            ("topk", CompressorConfig(kind="topk", ratio=0.25, block=128)),
            ("quant4", CompressorConfig(kind="quant", bits=4, block=128))):
        t = transports.get_transport(ccfg, "packed")
        msgs = jax.jit(
            lambda d: flat.FlatTransport(t, spec).codec.pack(d))(deltas)
        base = None
        for k in ks:
            ft = flat.FlatTransport(t, spec, cohorts=k)
            us, v = timed(jax.jit(lambda ms, w: ft.reduce(ms, w, m)),
                          msgs, weights, iters=iters)
            v = np.asarray(v)
            if k == 1:
                base = v
            dev = float(np.max(np.abs(v - base)))
            rec = {"n": n, "kind": name, "cohorts": k, "d": spec.d,
                   "us_reduce": round(us, 1),
                   "max_dev_vs_flat": dev}
            records.append(rec)
            emit(f"scale_twotier_{name}_k{k}", us,
                 f"max_dev={dev:.2e}")
    return records


# ---------------------------------------------------------------------------
# Smoke (CI guard)
# ---------------------------------------------------------------------------

def _final_leaves(cfg, params, batches, T=4):
    state = rounds.init_state(params, cfg)
    step = jax.jit(lambda s, b: rounds.round_step(s, b, _loss_pair, cfg))
    for _ in range(T):
        state, _ = step(state, batches)
    return jax.tree_util.tree_leaves(state.w)


def smoke(n=64, slack=1.5) -> int:
    key = jax.random.PRNGKey(0)
    params = _init_params(key)
    spec = flat.spec_of(params)
    batches = _batches(jax.random.fold_in(key, 1), n)
    m = n // 4

    # 1. slot-store parity: cap >= n is bit-identical to the dense gather
    # engine across wire formats
    for name, comm, up in (
            ("topk/packed", "packed",
             CompressorConfig(kind="topk", ratio=0.25, block=32)),
            ("quant4/packed", "packed",
             CompressorConfig(kind="quant", bits=4, block=32)),
            ("topk/dense", "dense",
             CompressorConfig(kind="topk", ratio=0.25, block=32))):
        dense = _final_leaves(_cfg(n, m, comm=comm, uplink=up),
                              params, batches)
        slot = _final_leaves(_cfg(n, m, comm=comm, uplink=up, cap=n),
                             params, batches)
        for a, b in zip(dense, slot):
            if not np.array_equal(np.asarray(a), np.asarray(b)):
                print(f"smoke: FAIL -- slot store (cap >= n) diverged from "
                      f"the dense gather engine on {name}")
                return 1
    print("smoke: slot store cap >= n bit-identical to dense gather "
          "(select/quant/dense wires) .. ok")

    # 2. evicting mode stays finite (cap = m: every round evicts)
    leaves = _final_leaves(_cfg(n, m, cap=m), params, batches, T=6)
    if not all(np.isfinite(np.asarray(x)).all() for x in leaves):
        print("smoke: FAIL -- evicting slot store produced non-finite "
              "trajectories")
        return 1
    print("smoke: evicting slot store (cap = m) trajectories finite .. ok")

    # 3. two-tier exactness.  Select payloads with integer-valued f32
    # entries, 0/1 weights and power-of-two rows make every cohort partial
    # an exact f32 sum, so the split must be BIT-equal for every k; quant
    # words are a reordered real-float sum -- pinned allclose.
    rows = 64
    ccfg = CompressorConfig(kind="topk", ratio=0.25, block=32)
    t = transports.get_transport(ccfg, "packed")
    ints = jnp.round(jax.random.normal(jax.random.fold_in(key, 2),
                                       (rows, spec.d)) * 100.0)
    w01 = (jax.random.uniform(jax.random.fold_in(key, 3), (rows,))
           < 0.5).astype(jnp.float32)
    msgs = jax.jit(lambda d: flat.FlatTransport(t, spec).codec.pack(d))(ints)
    ref = None
    for k in (1, 2, 4, 8, 16):
        ft = flat.FlatTransport(t, spec, cohorts=k)
        v = np.asarray(jax.jit(
            lambda ms, w: ft.reduce(ms, w, float(rows)))(msgs, w01))
        if k == 1:
            ref = v
        elif not np.array_equal(v, ref):
            print(f"smoke: FAIL -- two-tier select reduce k={k} not "
                  "bit-equal to flat on integer payloads")
            return 1
    qcfg = CompressorConfig(kind="quant", bits=4, block=32)
    tq = transports.get_transport(qcfg, "packed")
    reals = jax.random.normal(jax.random.fold_in(key, 4), (rows, spec.d))
    qmsgs = jax.jit(
        lambda d: flat.FlatTransport(tq, spec).codec.pack(d))(reals)
    qref = None
    for k in (1, 2, 4, 8, 16):
        ft = flat.FlatTransport(tq, spec, cohorts=k)
        v = np.asarray(jax.jit(
            lambda ms, w: ft.reduce(ms, w, float(rows)))(qmsgs, w01))
        if k == 1:
            qref = v
        else:
            np.testing.assert_allclose(v, qref, rtol=1e-5, atol=1e-6)
    print("smoke: two-tier reduce bit-equal (select, every k) / allclose "
          "(quant) vs flat .. ok")

    # 4. memory: the slot store must beat the dense residual >= 16x at the
    # top of the sweep (array arithmetic -- machine-independent)
    store = slots.init(NS[-1], CAP, spec.d, spec.dtype)
    slot_b = slots.resident_bytes(store)
    dense_b = _dense_ef_bytes(NS[-1], spec.d)
    print(f"smoke: EF bytes at n={NS[-1]}: dense={dense_b} "
          f"slots={slot_b} ({dense_b / slot_b:.0f}x)")
    if dense_b < 16 * slot_b:
        print("smoke: FAIL -- slot store saves < 16x at the sweep top")
        return 1

    # 5. regression: slot mode (cap >= n) vs the same-run dense gather
    # round.  Same-run comparison is machine-independent; the recorded
    # BENCH_scale.json baseline may excuse a borderline relative reading.
    us_dense = min(_time_round(_cfg(n, m), params, batches,
                               iters=3, warmup=2) for _ in range(2))
    us_slot = min(_time_round(_cfg(n, m, cap=n), params, batches,
                              iters=3, warmup=2) for _ in range(2))
    print(f"smoke: slot round {us_slot:.0f}us vs same-run dense gather "
          f"{us_dense:.0f}us (limit {us_dense * slack:.0f})")
    if us_slot > us_dense * slack:
        over = True
        try:
            with open("BENCH_scale.json") as f:
                base = json.load(f)["records"]["rounds"]
            want = next((r for r in base if r["n"] == NS[0]), None)
            if want and want["us_dense_round"]:
                lim = want["us_slot_round"] / want["us_dense_round"] \
                    * slack * us_dense
                print(f"smoke: vs BENCH_scale.json ratio baseline "
                      f"(limit {lim:.0f})")
                over = us_slot > lim
        except (FileNotFoundError, KeyError, StopIteration):
            pass
        if over:
            print("smoke: FAIL -- slot-mode round too slow vs the dense "
                  "gather round")
            return 1
    print("smoke: ok")
    return 0


# ---------------------------------------------------------------------------
# Sharded timing (4 forced host-platform devices, subprocess)
# ---------------------------------------------------------------------------

def sharded_worker(n=4096, m=M, iters=5):
    """Runs INSIDE the forced-4-device subprocess: time the scatter-sharded
    client gather (``shard.sharded_take``) under an active 4-way mesh vs
    the meshless single-device take on the same [n, PER, D] population, and
    print one JSON record per line."""
    from repro.scale import shard
    from repro.sharding import partition

    ndev = jax.device_count()
    key = jax.random.PRNGKey(0)
    data = {"x": jax.random.normal(key, (n, PER, D)),
            "y": jax.random.normal(jax.random.fold_in(key, 1), (n, PER))}
    idx = jax.random.randint(jax.random.fold_in(key, 2), (m,), 0, n)

    us_plain, _ = timed(jax.jit(lambda d, i: shard.sharded_take(d, i)),
                        data, idx, iters=iters)
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()).reshape(ndev),
                             ("data",))
    partition.activate_mesh(mesh)
    try:
        take = jax.jit(lambda d, i: shard.sharded_take(d, i))
        us_mesh, out = timed(take, data, idx, iters=iters)
        for leaf, ref in zip(jax.tree_util.tree_leaves(out),
                             (data["x"][idx], data["y"][idx])):
            np.testing.assert_array_equal(np.asarray(leaf), np.asarray(ref))
    finally:
        partition.activate_mesh(None)
    rec = {"n": n, "m": m, "devices": ndev,
           "us_take_meshless": round(us_plain, 1),
           "us_take_sharded": round(us_mesh, 1),
           "gather_exact": True}
    print("SHARDED-RECORD " + json.dumps(rec))
    return 0


def sharded_records(out: str = "BENCH_scale.json"):
    """Re-exec this module in a subprocess with 4 forced host-platform
    devices, collect the sharded-take timing record, and merge it into the
    ``sharded`` family of ``out`` (host CPU timings of a forced device
    mesh: a parity/latency probe, not an accelerator measurement)."""
    import os
    import subprocess

    env = dict(os.environ)
    # forced host devices exist only on the CPU backend; pinning it also
    # keeps the child off any accelerator this process may hold
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4").strip()
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.scale_bench", "--sharded-worker"],
        capture_output=True, text=True, env=env, timeout=900)
    if proc.returncode != 0:
        sys.stderr.write(proc.stdout + proc.stderr)
        raise RuntimeError("sharded worker subprocess failed")
    records = [json.loads(line.split(" ", 1)[1])
               for line in proc.stdout.splitlines()
               if line.startswith("SHARDED-RECORD ")]
    try:
        with open(out) as f:
            table = json.load(f)
    except FileNotFoundError:
        table = {"bench": "scale", "records": {}}
    table["records"]["sharded"] = records
    with open(out, "w") as f:
        json.dump(table, f, indent=1)
    for rec in records:
        emit(f"scale_sharded_take_n{rec['n']}", rec["us_take_sharded"],
             f"meshless={rec['us_take_meshless']};devices={rec['devices']}")
    return records


def scale_table(out: str = "BENCH_scale.json"):
    records = {"memory": memory_records(), "rounds": round_records(),
               "twotier": twotier_records()}
    with open(out, "w") as f:
        json.dump({"bench": "scale", "records": records}, f, indent=1)
    return records


ALL = [scale_table]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI guard (slot parity + two-tier exactness + "
                         "memory + regression)")
    ap.add_argument("--sharded", action="store_true",
                    help="time sharded_take under a forced 4-device mesh "
                         "(subprocess) and merge into BENCH_scale.json")
    ap.add_argument("--sharded-worker", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--out", default="BENCH_scale.json")
    ap.add_argument("--n", type=int, default=64)
    args = ap.parse_args()
    if args.smoke:
        sys.exit(smoke(n=args.n))
    if args.sharded_worker:
        sys.exit(sharded_worker())
    if args.sharded:
        print("name,us_per_call,derived")
        records = sharded_records(args.out)
        print(f"merged {len(records)} sharded records into {args.out}",
              file=sys.stderr)
        return
    print("name,us_per_call,derived")
    records = scale_table(args.out)
    n = sum(len(v) for v in records.values())
    print(f"wrote {args.out} ({n} records)", file=sys.stderr)


if __name__ == "__main__":
    main()
