import os
os.environ["XLA_FLAGS"] = (os.environ.get("DRYRUN_XLA_EXTRA", "") +
                           " --xla_force_host_platform_device_count=512").strip()
# Multi-pod dry-run: lower + compile every (arch × input-shape × mesh)
# combination on placeholder devices; print memory/cost analysis and derive
# roofline terms (launch/roofline.py).  MUST be run as a fresh process (the
# device count above is locked at first jax init -- hence lines 1-2, before
# ANY other import).
#
# Usage:
#   PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-4b --shape train_4k --mesh single
#   PYTHONPATH=src python -m repro.launch.dryrun --sweep --out results/dryrun.jsonl

import argparse
import json
import subprocess
import sys
import time
import traceback

from repro.configs.base import INPUT_SHAPES


def run_one(arch: str, shape_name: str, mesh_kind: str, comm: str = "dense",
            local_steps: int = 1, uplink_ratio: float = 0.1,
            dtype: str = None, seq_shard: bool = False,
            participation: str = "mask", client_chunk: int = 0,
            sampler: str = "uniform", async_buffer: bool = False,
            staleness: str = "constant", obs: bool = False,
            verbose: bool = True) -> dict:
    import jax
    from repro import configs
    from repro.launch import roofline, steps
    from repro.launch.mesh import make_production_mesh
    from repro.obs import log as obs_log

    t0 = time.time()
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    chips = mesh.devices.size
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
           "chips": chips, "comm": comm, "local_steps": local_steps,
           "uplink_ratio": uplink_ratio, "dtype": dtype or "default",
           "seq_shard": seq_shard, "participation": participation,
           "client_chunk": client_chunk, "sampler": sampler,
           "async_buffer": async_buffer, "staleness": staleness,
           "obs": obs}

    reason = steps.skip_reason(arch, shape_name)
    if reason:
        rec.update(status="skip", reason=reason)
        return rec

    case = steps.build_case(arch, shape_name, mesh, comm=comm,
                            local_steps=local_steps, dtype=dtype,
                            seq_shard=seq_shard, uplink_ratio=uplink_ratio,
                            participation=participation,
                            client_chunk=client_chunk, sampler=sampler,
                            async_buffer=async_buffer, staleness=staleness,
                            obs=obs) \
        if shape_name == "train_4k" else \
        steps.build_case(arch, shape_name, mesh, dtype=dtype)
    with mesh:
        lowered = jax.jit(case.fn).lower(*case.args)
        compiled = lowered.compile()

    mem = roofline.memory_summary(compiled)
    cost = roofline.cost_summary(compiled)
    coll = roofline.collective_bytes(compiled.as_text())
    cfg = configs.get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    n_tokens = (shape.global_batch * shape.seq_len
                if shape.kind != "decode" else shape.global_batch)
    mf = (roofline.model_flops(cfg, n_tokens) * max(local_steps, 1)
          if shape.kind == "train"
          else roofline.model_flops_forward(cfg, n_tokens))
    # XLA's cost analysis counts while-loop (lax.scan) bodies once, not
    # x trip-count, so per-device HLO flops undercount deep stacks; use the
    # analytic MODEL_FLOPS floor for the compute term, and apply the layer
    # trip count to loop-body collectives (EXPERIMENTS.md §Roofline).
    flops_eff = max(cost["flops"], mf / chips)
    coll_eff = roofline.corrected_collective_bytes(coll, cfg.n_layers)
    terms = roofline.roofline_terms(flops_eff, cost["bytes"],
                                    coll_eff, chips,
                                    roofline.DRYRUN_DEVICE_KIND)
    terms["collective_bytes_raw"] = coll["total"]
    terms["collective_bytes_corrected"] = coll_eff
    rec.update(
        status="ok",
        compile_s=round(time.time() - t0, 1),
        memory=mem, cost=cost,
        collectives={k: v for k, v in coll.items() if v},
        roofline=terms,
        model_flops=mf,
        useful_flops_ratio=(mf / (chips * cost["flops"])
                            if cost["flops"] else 0.0),
        n_params=cfg.n_params(), n_active_params=cfg.n_active_params(),
    )
    if verbose:
        obs_log.log(f"== {arch} × {shape_name} × {mesh_kind} ({chips} chips) ==")
        obs_log.log(f"  memory_analysis: {json.dumps(mem)}")
        obs_log.log(f"  cost_analysis: flops={cost['flops']:.3e} bytes={cost['bytes']:.3e}")
        obs_log.log(f"  collectives: {rec['collectives']}")
        obs_log.log(f"  roofline: compute={terms['compute_s']:.4f}s "
                    f"memory={terms['memory_s']:.4f}s coll={terms['collective_s']:.4f}s "
                    f"-> {terms['dominant']}-bound")
        obs_log.log(f"  MODEL_FLOPS={mf:.3e} useful/HLO={rec['useful_flops_ratio']:.3f}")
    return rec


def sweep(out_path: str, archs=None, shapes=None, meshes=("single", "multi"),
          comm="dense", timeout_s: int = 1800):
    """Run every combination in an isolated subprocess, appending JSONL."""
    from repro import configs as _c
    archs = archs or _c.all_arch_names()
    shapes = shapes or list(INPUT_SHAPES)
    for arch in archs:
        for shape in shapes:
            for mesh in meshes:
                cmd = [sys.executable, "-m", "repro.launch.dryrun",
                       "--arch", arch, "--shape", shape, "--mesh", mesh,
                       "--comm", comm, "--append", out_path]
                print(">>", arch, shape, mesh, flush=True)
                try:
                    subprocess.run(cmd, timeout=timeout_s, check=False)
                except subprocess.TimeoutExpired:
                    with open(out_path, "a") as f:
                        f.write(json.dumps({
                            "arch": arch, "shape": shape, "mesh": mesh,
                            "comm": comm, "status": "timeout"}) + "\n")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--shape", default="train_4k",
                    choices=list(INPUT_SHAPES))
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--comm", default="dense", choices=["dense", "packed", "pallas"])
    ap.add_argument("--local-steps", type=int, default=1)
    ap.add_argument("--uplink-ratio", type=float, default=0.1)
    ap.add_argument("--participation", default="mask",
                    choices=["mask", "gather"],
                    help="engine client-sampling execution (DESIGN.md §Engine)")
    ap.add_argument("--client-chunk", type=int, default=0,
                    help="lax.map over chunks of this many vmapped clients")
    ap.add_argument("--sampler", default="uniform",
                    choices=["uniform", "weighted"],
                    help="client-sampling law (repro.fleet.samplers; the "
                         "stateless laws lower under the abstract dry-run)")
    ap.add_argument("--async-buffer", action="store_true",
                    help="lower the asynchronous buffered round "
                         "(engine.async_rounds): staleness buffer becomes "
                         "an extra abstract input")
    ap.add_argument("--staleness", default="constant",
                    choices=["constant", "poly", "constraint"],
                    help="staleness-decay law for the async round")
    ap.add_argument("--obs", action="store_true",
                    help="lower the instrumented round (in-jit telemetry "
                         "bus, repro.obs): telemetry becomes extra scan "
                         "outputs in the compiled step")
    ap.add_argument("--log-level", default="info",
                    help="log threshold for the analysis report "
                         "(repro.obs.log)")
    ap.add_argument("--quiet", action="store_true",
                    help="shorthand for --log-level warning")
    ap.add_argument("--dtype", default=None, choices=[None, "float32", "bfloat16"])
    ap.add_argument("--seq-shard", action="store_true")
    ap.add_argument("--append", default=None, help="append JSONL record here")
    ap.add_argument("--sweep", action="store_true")
    ap.add_argument("--out", default="results/dryrun.jsonl")
    ap.add_argument("--archs", default=None, help="comma list for sweep")
    ap.add_argument("--shapes", default=None)
    ap.add_argument("--meshes", default="single,multi")
    args = ap.parse_args()

    from repro.obs import log as obs_log
    obs_log.set_level("warning" if args.quiet else args.log_level)

    if args.sweep:
        import os as _os
        _os.makedirs(_os.path.dirname(args.out) or ".", exist_ok=True)
        sweep(args.out,
              archs=args.archs.split(",") if args.archs else None,
              shapes=args.shapes.split(",") if args.shapes else None,
              meshes=tuple(args.meshes.split(",")), comm=args.comm)
        return

    try:
        rec = run_one(args.arch, args.shape, args.mesh, comm=args.comm,
                      local_steps=args.local_steps,
                      uplink_ratio=args.uplink_ratio,
                      dtype=args.dtype, seq_shard=args.seq_shard,
                      participation=args.participation,
                      client_chunk=args.client_chunk, sampler=args.sampler,
                      async_buffer=args.async_buffer,
                      staleness=args.staleness, obs=args.obs)
    except Exception as e:  # noqa: BLE001
        rec = {"arch": args.arch, "shape": args.shape, "mesh": args.mesh,
               "comm": args.comm, "status": "error",
               "error": f"{type(e).__name__}: {e}",
               "trace": traceback.format_exc()[-2000:]}
        print(rec["error"])
        print(rec["trace"])
    if args.append:
        import os as _os
        _os.makedirs(_os.path.dirname(args.append) or ".", exist_ok=True)
        with open(args.append, "a") as f:
            slim = dict(rec)
            slim.pop("trace", None)
            f.write(json.dumps(slim) + "\n")
    sys.exit(0 if rec.get("status") in ("ok", "skip") else 1)


if __name__ == "__main__":
    main()
