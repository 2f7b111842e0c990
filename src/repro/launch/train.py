"""Training launcher: run FedSGM rounds for any assigned architecture.

The model runs at its published widths unless ``--reduced`` asks for the
cut-down smoke-test variant (``configs.reduce_model``); the device count
never chooses it.  On a CPU:

    PYTHONPATH=src python -m repro.launch.train --arch smollm-360m --reduced \
        --rounds 20 --seq 64 --batch 2

``main(argv)`` is the same entry point in-process (``chip_smoke.py`` calls
it).  The sink's opening record names the platform, device kind and device
count the run used, and JAX's persistent compilation cache is configured
before the first compile (:mod:`repro.launch.compile_cache`).

Observability (DESIGN.md §Obs): ``--obs`` turns on the in-jit telemetry
bus, ``--sink {stdout,jsonl,memory}`` selects where per-round records go
(``--sink-path`` the JSONL file), ``--profile start:stop`` captures a
Perfetto trace for that round window, ``--log-level``/``--quiet`` gate
the launcher's own chatter.
"""
from __future__ import annotations

import argparse
import time
from typing import NamedTuple

import jax
import numpy as np

from repro import configs
from repro.configs.base import (AsyncConfig, CompressorConfig, FedConfig,
                                FleetConfig, ObsConfig, ScaleConfig,
                                SwitchConfig)
from repro.core import fedsgm
from repro.data import synthetic
from repro.engine import rounds
from repro.launch import compile_cache
from repro.models import build
from repro.obs import log as obs_log
from repro.obs import sinks as obs_sinks
from repro.obs import trace as obs_trace
from repro.sharding import partition
from repro.tasks import lm


def _run_wire(args, cfg):
    """``--wire K``: run the rounds over K real worker processes
    (repro.wire.coordinator.wire_drive) on the reduced LM problem.  The
    wire drives the pinned parity surface, so the single-process launcher
    with the same flags is its bit-exact oracle; per-round wire telemetry
    rides the selected sink."""
    for on, name in ((args.fleet, "--fleet"),
                     (args.async_buffer, "--async-buffer"),
                     (args.obs, "--obs"), (args.multi_pod, "--multi-pod"),
                     (args.ef_slots, "--ef-slots")):
        if on:
            raise SystemExit(
                f"--wire drives the pinned parity surface of repro.wire "
                f"(coordinator.validate_wire_cfg): {name} is not drivable "
                "over the wire -- drop one of the two flags")
    from repro import checkpoint
    from repro.wire import coordinator as wire_coordinator

    n = args.clients
    fed = FedConfig(
        n_clients=n, m=args.participating or n,
        local_steps=args.local_steps, lr=args.lr,
        switch=SwitchConfig(mode=args.switch, eps=0.0, beta=2.0),
        uplink=CompressorConfig(kind=args.uplink, ratio=args.ratio),
        downlink=CompressorConfig(kind="none"),
        comm=args.comm, strategy=args.strategy,
        participation="gather", full_eval=True, lean_metrics=True,
        client_chunk=args.client_chunk,
        fleet=FleetConfig(sampler=args.sampler))
    sink = obs_sinks.get_sink(
        args.sink, **({"path": args.sink_path} if args.sink == "jsonl"
                      else {}))
    sink.open(meta={"arch": cfg.name, "rounds": args.rounds,
                    "comm": args.comm, "strategy": args.strategy,
                    "wire_workers": args.wire, **device_meta()})
    resume = bool(args.ckpt_dir
                  and checkpoint.latest_round(args.ckpt_dir) is not None)
    faults = None
    if args.wire_heartbeat > 0:
        from repro.wire.supervisor import WireFaultConfig
        faults = WireFaultConfig(heartbeat_s=args.wire_heartbeat,
                                 min_quorum=args.min_quorum,
                                 max_respawns=args.max_respawns)
    t0 = time.time()
    state, mets, stats = wire_coordinator.wire_drive(
        fed, args.rounds, workers=args.wire, problem="lm",
        problem_args={"arch": args.arch, "n_clients": n,
                      "batch": args.batch, "seq": args.seq},
        sink=sink, deadline=args.wire_deadline, faults=faults,
        ckpt_dir=args.ckpt_dir,
        ckpt_every=10 if args.ckpt_dir else 0, resume=resume,
        progress=lambda t, f, g, s: obs_log.log(
            f"wire round {t}: f={float(f):.4f} g_hat={float(g):.4f} "
            f"sigma={float(s):.2f}"))
    sink.close()
    wall = time.time() - t0
    obs_log.log(
        f"wire run done: {args.rounds} rounds over {args.wire} workers in "
        f"{wall:.1f}s ({stats.totals['frames']} frames, "
        f"{stats.totals['bytes']} bytes, "
        f"missing={stats.totals['missing']}, "
        f"rejected={stats.totals['rejected']}, "
        f"respawns={stats.totals['respawns']}, "
        f"degraded={stats.totals['degraded']})")
    return state


class TrainRun(NamedTuple):
    """What :func:`main` ran: the final state and, on the host-batch path,
    the jitted round program with one batch of its input shape (so a caller
    can inspect the compiled program)."""
    state: object
    step: object = None
    batch: object = None


def device_meta() -> dict:
    """What the run executes on, as JAX reports it."""
    dev = jax.devices()[0]
    return {"platform": dev.platform, "device_kind": dev.device_kind,
            "device_count": jax.device_count()}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--reduced", action="store_true",
                    help="cut-down smoke-test widths (configs.reduce_model)"
                         " instead of the published config")
    ap.add_argument("--rounds", type=int, default=20)
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--participating", type=int, default=0)
    ap.add_argument("--local-steps", type=int, default=1)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--batch", type=int, default=2, help="per-client batch")
    ap.add_argument("--lr", type=float, default=0.03)
    ap.add_argument("--uplink", default="topk", choices=["none", "topk", "quant"])
    ap.add_argument("--ratio", type=float, default=0.1)
    ap.add_argument("--comm", default="dense", choices=["dense", "packed", "pallas"])
    ap.add_argument("--switch", default="soft", choices=["hard", "soft"])
    ap.add_argument("--strategy", default="fedsgm",
                    help="engine strategy (repro.engine.strategies registry)")
    ap.add_argument("--participation", default="mask",
                    choices=["mask", "gather"],
                    help="dense-mask simulation vs compute-sparse gather of "
                         "the m sampled clients")
    ap.add_argument("--client-chunk", type=int, default=0,
                    help="lax.map over chunks of this many vmapped clients")
    ap.add_argument("--fleet", action="store_true",
                    help="device-resident client fleet with in-jit minibatch "
                         "provisioning (repro.fleet): the whole multi-round "
                         "driver runs jitted, no per-round host batches")
    ap.add_argument("--fleet-pool", type=int, default=8,
                    help="token sequences held per client (fleet mode)")
    ap.add_argument("--sampler", default="uniform",
                    choices=["uniform", "weighted", "markov"],
                    help="client-sampling law (repro.fleet.samplers)")
    ap.add_argument("--async-buffer", action="store_true",
                    help="asynchronous buffered rounds (engine.async_rounds,"
                         " DESIGN.md §Async): clients lost mid-round park "
                         "their compressed uplink in a staleness buffer and "
                         "merge into a later server update")
    ap.add_argument("--staleness", default="constant",
                    choices=["constant", "poly", "constraint"],
                    help="staleness-decay law for buffered uplinks")
    ap.add_argument("--max-staleness", type=int, default=4,
                    help="a buffered uplink may merge up to this age "
                         "(rounds); entries that reach it undelivered "
                         "expire")
    ap.add_argument("--depart", type=float, default=0.25,
                    help="mid-round departure probability for samplers "
                         "without an availability model (markov uses its "
                         "own chain)")
    ap.add_argument("--ef-slots", type=int, default=0,
                    help="capacity of the O(cap*d) uplink EF slot store "
                         "(repro.scale.slots) replacing the dense [n, d] "
                         "residual; requires --participation gather and "
                         "cap >= m.  0 keeps the dense residual")
    ap.add_argument("--cohorts", type=int, default=1,
                    help="hierarchical two-tier payload aggregation: this "
                         "many edge reducers each reduce their cohort's "
                         "payloads, the server sums the partials")
    ap.add_argument("--obs", action="store_true",
                    help="in-jit telemetry bus (repro.obs, DESIGN.md §Obs): "
                         "per-round optimizer-health counters ride the "
                         "metric offload; off is bit-for-bit the plain "
                         "engine")
    ap.add_argument("--obs-window", type=int, default=8,
                    help="trailing window (rounds) for the switching "
                         "fraction telemetry")
    ap.add_argument("--sink", default="stdout",
                    choices=list(obs_sinks.sink_names()),
                    help="per-round metric destination "
                         "(repro.obs.sinks registry)")
    ap.add_argument("--sink-path", default="metrics.jsonl",
                    help="output file for --sink jsonl")
    ap.add_argument("--log-level", default="info",
                    choices=list(obs_log.LEVELS),
                    help="launcher log threshold (repro.obs.log)")
    ap.add_argument("--quiet", action="store_true",
                    help="shorthand for --log-level warning (silences the "
                         "stdout sink's progress lines too)")
    ap.add_argument("--profile", default=None, metavar="START:STOP",
                    help="capture a jax.profiler trace while START <= round "
                         "< STOP (Perfetto-viewable dir under profiles/)")
    ap.add_argument("--multi-pod", action="store_true",
                    help="use the production mesh (needs devices)")
    ap.add_argument("--ckpt-dir", default=None,
                    help="save/restore round checkpoints here")
    ap.add_argument("--wire", type=int, default=0, metavar="K",
                    help="cross-process federation (repro.wire, DESIGN.md "
                         "§Wire): spawn K worker processes over loopback "
                         "TCP, each owning a contiguous client range; the "
                         "coordinator drives the pinned parity surface "
                         "(gather participation, full eval, lean metrics). "
                         "Per-round wire telemetry (frames, bytes, frame "
                         "latency, fault counters) flows through --sink")
    ap.add_argument("--wire-deadline", type=float, default=120.0,
                    help="per-collection deadline (seconds) before a "
                         "missing worker frame is treated as dead/droppable")
    ap.add_argument("--wire-heartbeat", type=float, default=0.0,
                    metavar="S",
                    help="arm the fault-tolerant wire runtime (repro.wire."
                         "supervisor): workers heartbeat every S seconds, "
                         "silence past 3*S declares a worker dead; dead "
                         "workers respawn with EF re-seed + round replay, "
                         "unrecoverable ones degrade the round (sampled "
                         "clients demoted, HT weights rescaled "
                         "mass-conservingly)")
    ap.add_argument("--min-quorum", type=float, default=0.5,
                    help="abort a degraded round when fewer than this "
                         "fraction of the m sampled clients are realized "
                         "(with --wire-heartbeat; default 0.5)")
    ap.add_argument("--max-respawns", type=int, default=2,
                    help="per-worker respawn budget for the wire "
                         "supervisor (with --wire-heartbeat; default 2)")
    args = ap.parse_args(argv)

    obs_log.set_level("warning" if args.quiet else args.log_level)
    compile_cache.configure()
    profile = obs_trace.ProfileWindow(args.profile)

    cfg = (configs.get_reduced(args.arch) if args.reduced
           else configs.get_config(args.arch))

    if args.wire:
        return TrainRun(_run_wire(args, cfg))

    if args.multi_pod:
        from repro.launch.mesh import make_production_mesh
        mesh = make_production_mesh(multi_pod=True)
        partition.activate_mesh(mesh)

    fns = build(cfg)
    key = jax.random.PRNGKey(0)
    params = fns.init(key, cfg)
    n = args.clients
    fed = FedConfig(
        n_clients=n, m=args.participating or n, local_steps=args.local_steps,
        lr=args.lr,
        switch=SwitchConfig(mode=args.switch, eps=0.0, beta=2.0),
        uplink=CompressorConfig(kind=args.uplink, ratio=args.ratio),
        downlink=CompressorConfig(kind="none"),
        comm=args.comm, strategy=args.strategy,
        participation=args.participation, client_chunk=args.client_chunk,
        fleet=FleetConfig(sampler=args.sampler, batch_size=args.batch,
                          redraw=True) if args.fleet else FleetConfig(
                              sampler=args.sampler),
        async_=AsyncConfig(enabled=args.async_buffer,
                           staleness=args.staleness,
                           max_staleness=args.max_staleness,
                           depart=args.depart),
        scale=ScaleConfig(ef_slots=args.ef_slots, cohorts=args.cohorts),
        obs=ObsConfig(enabled=args.obs, window=args.obs_window))
    loss_pair = lm.make_loss_pair(fns.forward, cfg, budget=6.0,
                                  aux_constraint=cfg.moe is not None)
    state = fedsgm.init_state(params, fed)
    start_round = 0
    if args.ckpt_dir:
        from repro import checkpoint
        restored, t0 = checkpoint.restore_round(args.ckpt_dir, state)
        if restored is not None:
            state, start_round = restored, t0
            obs_log.log(f"restored checkpoint at round {t0}")

    sink = obs_sinks.get_sink(
        args.sink, **({"path": args.sink_path} if args.sink == "jsonl" else {}))
    sink.open(meta={"arch": cfg.name, "rounds": args.rounds,
                    "comm": args.comm, "strategy": args.strategy,
                    "participation": args.participation,
                    "async_buffer": args.async_buffer, "obs": args.obs,
                    "start_round": start_round, **device_meta()})

    t0 = time.time()
    if args.fleet:
        if cfg.family in ("vlm", "audio"):
            raise SystemExit(
                f"--fleet does not support --arch {args.arch} yet: "
                f"repro.tasks.lm.make_fleet builds token-only pools, and "
                f"{cfg.family} archs need per-client media-embedding shards "
                "that no fleet partitioner provides (ROADMAP.md open item "
                "'Media pools'; limitation documented in README.md).  "
                "Either drop --fleet to use the host batch_fn path, which "
                "synthesizes media embeddings per round, or pick a "
                "token-only arch (e.g. --arch smollm-360m, qwen3-4b, "
                "mamba2-130m).")
        from repro.engine import async_rounds
        fleet = lm.make_fleet(jax.random.PRNGKey(1), fed,
                              pool=args.fleet_pool, seq_len=args.seq,
                              vocab=cfg.vocab, hetero=0.5)
        buf = async_rounds.init_buffer(state.w, fed)
        if args.ckpt_dir and start_round and args.async_buffer:
            from repro import checkpoint
            wire = checkpoint.restore_buffer(
                args.ckpt_dir, start_round,
                async_rounds.buffer_wire_struct(state.w, fed))
            if wire is not None:
                buf = async_rounds.buffer_from_wire(wire, state.w, fed)
                obs_log.log(f"restored staleness buffer at round "
                            f"{start_round}")
        for chunk in range(max(args.rounds // 10, 1)):
            profile.tick(start_round + 10 * chunk)
            if args.async_buffer:
                state, buf, hist = async_rounds.async_drive(
                    state, fleet, loss_pair, fed, T=10, buf=buf)
            else:
                state, hist = fedsgm.drive(state, fleet, loss_pair, fed,
                                           T=10)
            done = start_round + 10 * (chunk + 1)
            for rec in obs_sinks.rows(
                    hist, start_round=done - 10,
                    s_per_round=(time.time() - t0) / (done - start_round)):
                sink.emit(rec)
            if args.ckpt_dir:
                from repro import checkpoint
                checkpoint.save_round(args.ckpt_dir, done, state,
                                      metadata={"arch": cfg.name},
                                      fleet=fleet, cfg=fed)
                checkpoint.save_buffer(
                    args.ckpt_dir, done,
                    async_rounds.buffer_wire(buf, state.w, fed))
        profile.close()
        sink.close()
        return TrainRun(state)

    def batch_fn(t, k):
        toks, mask = synthetic.client_token_batches(
            k, n, args.batch, args.seq, cfg.vocab, hetero=0.5)
        media = None
        if cfg.family in ("vlm", "audio"):
            M = cfg.n_media_tokens or cfg.n_audio_frames
            media = jax.random.normal(
                k, (n, args.batch, M, cfg.d_media or cfg.d_model)) * 0.02
        return lm.LMBatch(tokens=toks, minority_mask=mask, media=media)

    astep = buf = None
    if args.async_buffer:
        from repro.engine import async_rounds
        buf = async_rounds.init_buffer(state.w, fed)
        astep = jax.jit(lambda s, b, batch: async_rounds.async_round_step(
            s, b, batch, loss_pair, fed))

    step = None if args.async_buffer else rounds.round_program(loss_pair, fed)
    for chunk in range(max(args.rounds // 10, 1)):
        profile.tick(start_round + 10 * chunk)
        if args.async_buffer:
            key = jax.random.PRNGKey(fed.seed + 1 + chunk)
            per_round = []
            for t in range(10):
                key, sub = jax.random.split(key)
                state, buf, h = astep(state, buf, batch_fn(t, sub))
                per_round.append(jax.device_get(h))
            hist = jax.tree_util.tree_map(
                lambda *xs: np.stack(xs), *per_round)
        else:
            state, hist = fedsgm.run_rounds(state, batch_fn, loss_pair,
                                            fed, T=10, step=step)
        done = start_round + 10 * (chunk + 1)
        for rec in obs_sinks.rows(
                hist, start_round=done - 10,
                s_per_round=(time.time() - t0) / (done - start_round)):
            sink.emit(rec)
        if args.ckpt_dir:
            from repro import checkpoint
            checkpoint.save_round(args.ckpt_dir, done, state,
                                  metadata={"arch": cfg.name})
            if args.async_buffer:
                from repro.engine import async_rounds
                checkpoint.save_buffer(
                    args.ckpt_dir, done,
                    async_rounds.buffer_wire(buf, state.w, fed))
    profile.close()
    sink.close()
    return TrainRun(state, step, batch_fn(0, jax.random.PRNGKey(0)))


if __name__ == "__main__":
    main()
