#!/usr/bin/env python3
"""Bring-up check: FedSGM rounds at full width on a TPU.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the client-axis mesh path, four chips

One process holds the chip for the whole run.  On one chip the phases run
in order, and any failure exits non-zero (no phase catches an error):

 1. the first JAX device is a TPU -- there is no CPU or interpret fallback;
 2. JAX's persistent compilation cache is configured
    (``repro.launch.compile_cache``);
 3. every Pallas kernel on the round path compiles, runs once at the shape
    of a full-width ``smollm-360m`` wire run and matches its XLA plan:
    top-k offsets equal, quant codes equal or one level apart (the count is
    printed), float sums allclose;
 4. ``repro.launch.train.main`` trains ``smollm-360m`` at its published
    widths for one chunk of 10 rounds with gather participation and the
    pallas wire, once with the quant uplink and once with top-k;
 5. ``f`` and ``g_hat`` are finite in every round;
 6. the compiled round program contains ``tpu_custom_call`` (the kernels
    ran compiled, not interpreted);
 7. after ``jax.clear_caches()`` the quant run repeats and its round
    program comes from the persistent cache.

Earlier lines report compile seconds, seconds per round (a bring-up
observation, not a benchmark), ``peak_bytes_in_use`` and the plan each
kernel key resolved to.  ``--chips 4`` runs only the client-axis path:
gather rounds of a depth-cut ``smollm-360m`` at full width with the client
axis on a ("data",) mesh of the four chips, against the same rounds on one
chip.  The last line of stdout is the JSON verdict.
"""
from __future__ import annotations

import argparse
import json
import math
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
OUT = ROOT / "chiprun_out"

ARCH = "smollm-360m"
# the largest client count, sequence and per-client batch whose full-width
# round fits one v5e's 16 GB (the compile's memory_analysis decides)
CLIENTS, SEQ, BATCH = 2, 512, 1
ROUNDS = 10                     # one train.py chunk
MESH_LAYERS, MESH_CLIENTS, MESH_ROUNDS = 8, 4, 3


def log(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


class CompileMeter:
    """Backend compile seconds and persistent-cache hits, from JAX's
    monitoring events (a cache hit reports its retrieval time as the
    compile duration)."""

    def __init__(self, jax):
        self.seconds, self.hits = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def mark(self):
        return self.seconds, self.hits


def peak_bytes(jax) -> int:
    return int(jax.devices()[0].memory_stats()["peak_bytes_in_use"])


# ---------------------------------------------------------------------------
# phase 3: the kernels against their XLA plans
# ---------------------------------------------------------------------------

def kernel_phase(jax, jnp, np):
    from repro import configs
    from repro.comm import flat, payloads
    from repro.configs.base import CompressorConfig
    from repro.kernels import ops
    from repro.kernels.quantize_ef_pack import quantize_ef_pack
    from repro.kernels.scatter_agg import scatter_agg, segment_rows
    from repro.kernels.topk_block import block_topk
    from repro.kernels.unpack_mma import unpack_mma
    from repro.models import build

    cfg = configs.get_config(ARCH)
    shapes = jax.eval_shape(lambda k: build(cfg).init(k, cfg),
                            jax.random.PRNGKey(0))
    spec = flat.spec_of(shapes)
    quant = CompressorConfig(kind="quant", bits=8)
    topk = CompressorConfig(kind="topk", ratio=0.1)
    # the largest run of the wire layout, as the round's kernels see it:
    # [clients, nblocks, block] (quant and top-k share the geometry)
    run = max(flat.wire_layout(spec, topk).runs, key=lambda r: r.span)
    n, nb, block, k, bits = CLIENTS, run.nblocks, run.block, run.k, 8
    log(f"kernels at the largest {ARCH} wire run: {n} clients x {nb} "
        f"blocks x {block}, top-k {k} (d = {spec.d})")
    key = jax.random.PRNGKey(7)
    e = jax.random.normal(key, (n, nb, block), jnp.float32) * 1e-3
    delta = jax.random.normal(jax.random.fold_in(key, 1), (n, nb, block),
                              jnp.float32)
    weight = jnp.linspace(0.5, 1.5, n, dtype=jnp.float32)
    close = lambda a, b, rtol, atol: np.testing.assert_allclose(  # noqa: E731
        np.asarray(a), np.asarray(b), rtol=rtol, atol=atol)

    # quantize_ef_pack vs quant_blocks + pack_codes (the packed backend)
    words, scale, e_new = quantize_ef_pack(e, delta, bits)
    buf = e + delta
    codes_ref, scale_ref = jax.jit(payloads.quant_blocks,
                                   static_argnums=1)(buf, bits)
    codes = payloads.unpack_codes(words, bits, block)
    diff = np.abs(np.asarray(codes) - np.asarray(codes_ref, np.int64))
    off_by_one = int((diff == 1).sum())
    log(f"quantize_ef_pack: {off_by_one} of {diff.size} codes one level "
        f"off the XLA quantizer; max level difference {int(diff.max())}")
    check(int(diff.max()) <= 1, "quant codes more than one level apart")
    close(scale, scale_ref[..., 0], 0.0, 0.0)
    v = codes.astype(jnp.float32) / float(2 ** (bits - 1) - 1) \
        * scale[..., None]
    close(e_new + v, buf, 1e-6, 1e-6)

    # unpack_mma vs unpack_codes + tensordot (the XLA quant_agg plan)
    acc = unpack_mma(words, scale, weight, bits, block)
    acc_ref = ops.quant_agg(words, scale, weight, bits, block,
                            plan=ops.tune.Plan("tensordot"))
    close(acc, acc_ref, 1e-5, 1e-6)
    log("unpack_mma: matches the tensordot plan")

    # block_topk vs lax.top_k: equal offsets, equal values
    vals, idx = block_topk(delta, k)           # slot rows padded to 128
    vals_ref, idx_ref = jax.jit(
        lambda a: payloads.select_topk_blocks(a, k, False))(delta)
    check(bool(jnp.all(idx[..., :k] == idx_ref.astype(jnp.int32))),
          "block_topk offsets differ from lax.top_k")
    close(vals[..., :k], vals_ref, 0.0, 0.0)
    log(f"block_topk: offsets and values equal to lax.top_k (k={k})")

    # scatter_agg vs the XLA scatter-add plan
    idx16 = idx.astype(payloads.INDEX_DTYPE)
    agg = scatter_agg(vals, idx16, weight, block, k=k)
    agg_ref = ops.scatter_agg(vals, idx16, weight, block=block, k=k,
                              plan=ops.tune.Plan("scatter"))
    close(agg, agg_ref, 1e-5, 1e-6)
    log("scatter_agg: matches the scatter-add plan")

    # segment_rows vs .at[].add on the [m, K_total] top-k value stream
    K = flat.wire_layout(spec, topk).K_total
    rows = jax.random.normal(jax.random.fold_in(key, 2), (n, K), jnp.float32)
    seg = jnp.arange(n, dtype=jnp.int32)[::-1]
    out = segment_rows(rows, seg, n)
    close(out, jnp.zeros_like(rows).at[seg].add(rows), 0.0, 0.0)
    log(f"segment_rows: matches .at[].add at [{n}, {K}]")
    return off_by_one


# ---------------------------------------------------------------------------
# phases 4-7: full-width training through repro.launch.train
# ---------------------------------------------------------------------------

def train_phase(jax, jnp, np, meter, uplink: str, tag: str):
    from repro.launch import train

    path = OUT / f"smoke_{tag}.jsonl"
    path.unlink(missing_ok=True)
    argv = ["--arch", ARCH, "--rounds", str(ROUNDS),
            "--clients", str(CLIENTS), "--seq", str(SEQ),
            "--batch", str(BATCH), "--participation", "gather",
            "--comm", "pallas", "--uplink", uplink,
            "--sink", "jsonl", "--sink-path", str(path)]
    log(f"[{tag}] python -m repro.launch.train {' '.join(argv)}")
    s0, h0 = meter.mark()
    t0 = time.perf_counter()
    run = train.main(argv)
    jax.block_until_ready(run.state)
    wall = time.perf_counter() - t0
    compile_s, hits = meter.seconds - s0, meter.hits - h0

    lines = [json.loads(x) for x in path.read_text().splitlines()]
    meta, recs = lines[0]["meta"], lines[1:]
    check(meta["platform"] == "tpu", f"train ran on {meta['platform']}")
    check(len(recs) == ROUNDS, f"{len(recs)} round records, not {ROUNDS}")
    for r in recs:
        check(math.isfinite(r["f"]) and math.isfinite(r["g_hat"]),
              f"[{tag}] round {r['round']}: f={r['f']} g_hat={r['g_hat']}")
    log(f"[{tag}] f: {recs[0]['f']:.4f} -> {recs[-1]['f']:.4f}; g_hat: "
        f"{recs[0]['g_hat']:.4f} -> {recs[-1]['g_hat']:.4f}; all finite")
    hlo = run.step.lower(run.state, run.batch).compile().as_text()
    ncalls = hlo.count("tpu_custom_call")
    check(ncalls > 0, f"[{tag}] no tpu_custom_call in the round program")
    log(f"[{tag}] round program: {ncalls} tpu_custom_call sites")
    log(f"[{tag}] compile {compile_s:.1f} s ({hits} persistent-cache "
        f"hits); {(wall - compile_s) / ROUNDS:.3f} s/round after compile "
        f"(bring-up observation, not a benchmark); peak_bytes_in_use "
        f"{peak_bytes(jax)}")
    return compile_s, hits


def one_chip(jax, jnp, np) -> None:
    from repro.kernels import tune
    from repro.launch import compile_cache

    log(f"compile cache: {compile_cache.configure()}")
    meter = CompileMeter(jax)
    kernel_phase(jax, jnp, np)
    jax.clear_caches()
    cold, _ = train_phase(jax, jnp, np, meter, "quant", "quant")
    train_phase(jax, jnp, np, meter, "topk", "topk")
    for key, plan in sorted(tune.table().items()):
        log(f"plan {key} -> {plan.impl} {plan.params}")
    jax.clear_caches()
    warm, hits = train_phase(jax, jnp, np, meter, "quant", "quant_warm")
    check(hits > 0, "the repeated quant run missed the persistent cache")
    log(f"compile seconds cold {cold:.1f}, warm {warm:.1f} "
        f"(after jax.clear_caches())")


# ---------------------------------------------------------------------------
# --chips 4: the client-axis mesh path against one chip
# ---------------------------------------------------------------------------

def four_chips(jax, jnp, np) -> None:
    import dataclasses

    from repro import configs
    from repro.configs.base import (CompressorConfig, FedConfig, FleetConfig,
                                    SwitchConfig)
    from repro.engine import rounds
    from repro.launch import compile_cache, roofline
    from repro.models import build
    from repro.scale import shard
    from repro.sharding import partition
    from repro.tasks import lm

    check(len(jax.devices()) == 4, f"{len(jax.devices())} devices, not 4")
    log(f"compile cache: {compile_cache.configure()}")
    cfg = dataclasses.replace(configs.get_config(ARCH),
                              n_layers=MESH_LAYERS)
    fns = build(cfg)
    # an uncompressed uplink: the sharded run reassociates cross-client
    # sums, and a compressor would turn those last-ulp differences into
    # whole quantization levels or swapped top-k slots
    fed = FedConfig(
        n_clients=MESH_CLIENTS, m=MESH_CLIENTS, lr=0.03,
        switch=SwitchConfig(mode="soft", eps=0.0, beta=2.0),
        uplink=CompressorConfig(kind="none"),
        downlink=CompressorConfig(kind="none"), comm="pallas",
        participation="gather", full_eval=False,
        fleet=FleetConfig(batch_size=BATCH, redraw=True))
    loss_pair = lm.make_loss_pair(fns.forward, cfg, budget=6.0)

    def trajectory():
        # a fresh jit per run: the active mesh changes the trace
        step = jax.jit(lambda s, f: rounds.round_step(s, f, loss_pair, fed))
        params = fns.init(jax.random.PRNGKey(0), cfg)
        fleet = lm.make_fleet(jax.random.PRNGKey(1), fed, pool=4,
                              seq_len=SEQ, vocab=cfg.vocab)
        if partition.current_mesh() is not None:
            fleet = jax.jit(shard.constrain_fleet)(fleet)
        state = rounds.init_state(params, fed)
        hlo = step.lower(state, fleet).compile().as_text()
        fs = []
        for _ in range(MESH_ROUNDS):
            state, mets = step(state, fleet)
            fs.append(float(mets.f))
        return jax.block_until_ready(state), fleet, fs, hlo

    t0 = time.perf_counter()
    ref, _, f_ref, _ = trajectory()
    log(f"one chip: {MESH_ROUNDS} gather rounds of {MESH_LAYERS}-layer "
        f"{ARCH} (d_model {cfg.d_model}, vocab {cfg.vocab}), "
        f"{MESH_CLIENTS} clients, f {f_ref} in "
        f"{time.perf_counter() - t0:.1f} s")
    ref_w = jax.tree_util.tree_map(np.asarray, ref.w)
    del ref
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()), ("data",))
    partition.activate_mesh(mesh)
    try:
        t0 = time.perf_counter()
        under, fleet, f_mesh, hlo = trajectory()
        log(f"4-chip mesh: f {f_mesh} in {time.perf_counter() - t0:.1f} s")
    finally:
        partition.activate_mesh(None)
    tokens = fleet.data.tokens
    devs = {s.device for s in tokens.addressable_shards}
    rows = {s.data.shape[0] for s in tokens.addressable_shards}
    log(f"fleet tokens {tokens.shape}: {len(devs)} devices, {sorted(rows)} "
        f"client rows each")
    check(len(devs) == 4 and rows == {MESH_CLIENTS // 4},
          "the client axis is not split over the 4 chips")
    coll = roofline.collective_bytes(hlo)
    log(f"mesh round program collectives (bytes): "
        f"{ {k: v for k, v in coll.items() if v} }")
    check(coll["total"] > 0, "no collective in the mesh round program")
    for a, b in zip(jax.tree_util.tree_leaves(ref_w),
                    jax.tree_util.tree_leaves(under.w)):
        np.testing.assert_allclose(a, np.asarray(b), rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(f_ref, f_mesh, rtol=1e-5)
    log("4-chip trajectory matches the one-chip run (rtol 1e-5, atol 1e-7)")
    peaks = [int(d.memory_stats()["peak_bytes_in_use"])
             for d in jax.devices()]
    log(f"peak_bytes_in_use per chip: {peaks}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4])
    args = ap.parse_args(argv)
    check((ROOT / "src" / "repro").is_dir(),
          f"no repro package under {ROOT / 'src'}: run from a checkout")
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    import jax.numpy as jnp
    import numpy as np

    dev = jax.devices()[0]
    check(dev.platform == "tpu",
          f"JAX found no TPU (first device: {dev.platform}); this check "
          "has no CPU or interpret-mode fallback")
    log(f"device {dev.device_kind} x {jax.device_count()}, jax "
        f"{jax.__version__}")
    OUT.mkdir(exist_ok=True)
    if args.chips == 4:
        four_chips(jax, jnp, np)
    else:
        check(jax.device_count() >= 1, "no device")
        one_chip(jax, jnp, np)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
