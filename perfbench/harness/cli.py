"""One run of one cell: set-up, the measured window, the check, one result
line.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

Set-up builds the round program, makes the weights on the device from the
seed, and drives the first rounds (``rounds`` in the cell's limits file)
through the window's own step and feed: they compile or load the program
from the persistent cache, warm it up, and give the readings the check
compares.  The window then drives the same program round after round from
the same state, the host enqueueing each round while the one before it
runs (at most two in flight, no transfer to the host), and ends when the
last round dispatched after ``--seconds`` completes.  Once it has closed,
the HBM in use while a round runs is read, the program's state is freed,
and the plain reference repeats the first rounds for the comparison.

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a profiler trace of the window.  The last line of
standard output is one JSON object; the numbers compared, with their
limits, come last in it (``checks``) and as the last lines of standard
error.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import sys
import time

from harness import spec

RUNS_DIR = "perfbench_runs"


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def window(prog, state, k_t, t_first: int, seconds: float):
    """The measured rounds.  Returns (state, metrics list, start, end)."""
    import jax
    from jax.profiler import TraceAnnotation
    mets, pending = [], []
    t = t_first
    start = time.perf_counter()
    with TraceAnnotation("perfbench.window"):
        while True:
            if len(pending) >= 2:
                with TraceAnnotation("perfbench.wait"):
                    pending.pop(0).block_until_ready()
            with TraceAnnotation("perfbench.gen_batch"):
                batch = prog.batch(k_t, t)
            with TraceAnnotation("perfbench.dispatch"):
                state, met = prog.step(state, batch)
            mets.append(met)
            pending.append(met.f)
            t += 1
            if time.perf_counter() - start >= seconds:
                break
        with TraceAnnotation("perfbench.drain"):
            jax.block_until_ready(state)
    return state, mets, start, time.perf_counter()


def bytes_in_use(dev) -> int:
    stats = dev.memory_stats() or {}
    return int(stats.get("bytes_in_use", 0))


def run(argv, root: str, t0: float, require_chip: bool = True,
        bench_dir: str = spec.BENCH_DIR) -> int:
    args = parse(argv)
    cell = spec.load_cell(root, args.workload, bench_dir)
    import jax
    import numpy as np

    from harness import peaks, program, reference, trace
    from harness.meter import CompileMeter
    meter = CompileMeter()
    devs = jax.devices()
    if require_chip and (devs[0].platform != "tpu"
                         or len(devs) < cell.chips):
        print(f"{cell.name} needs {cell.chips} TPU chip(s); JAX found "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        return 3
    dev = devs[0]
    pk = peaks.peaks(dev.device_kind) if require_chip else \
        peaks.PEAKS.get(dev.device_kind)

    ref_mod = spec.load_reference(cell.config["name"], bench_dir)
    prog = program.Program(cell.config, cell.traffic, ref_mod)
    checked = int(cell.limits["rounds"])
    state, keys = prog.state(args.seed)
    state, readings = prog.first_rounds(state, keys, checked)
    setup_compile_s, setup_compiles, setup_hits = meter.mark()
    print(f"set-up: {setup_compiles} compiles, {setup_hits} persistent-cache "
          f"hits, {setup_compile_s:.3f} s of backend compile; wire bytes of "
          f"a round (RoundMetrics): up {prog.wire_bytes[0]} per client, "
          f"down {prog.wire_bytes[1]}", file=sys.stderr)

    trace_dir = os.path.join(root, RUNS_DIR,
                             f"trace-{cell.name}-{args.seed}")
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
    setup_s = time.perf_counter() - t0
    state, mets, w_start, w_end = window(prog, state, keys[2], checked,
                                         args.seconds)
    if args.trace:
        jax.profiler.stop_trace()
    in_window = meter.mark()[1] - setup_compiles
    # HBM in use while a round runs: the allocator counts the state (the
    # round's arguments, still held) and the batches; the runtime sets the
    # compiled round's scratch aside outside that count
    mem = prog.step.memory_analysis()
    hbm = bytes_in_use(dev) + int(mem.temp_size_in_bytes)
    fg = np.asarray(jax.device_get([(m.f, m.g_hat) for m in mets]))
    attempted = len(mets)
    failed = int(np.sum(~np.isfinite(fg).all(axis=1)))
    del state, mets
    tokens_per_s = attempted * prog.tokens_per_round / (w_end - w_start)
    print(f"window: {attempted} rounds in {w_end - w_start:.3f} s, "
          f"{in_window} compiles inside it; HBM {hbm} B in use while a "
          f"round runs; round program (memory_analysis): arguments "
          f"{mem.argument_size_in_bytes} B, scratch "
          f"{mem.temp_size_in_bytes} B, its own peak "
          f"{mem.peak_memory_in_bytes} B", file=sys.stderr)

    summary = None
    if args.trace:
        # the round program's HLO names each op's stage through its
        # metadata
        summary = trace.summarize(trace_dir, attempted, prog.step.as_text())
        shutil.rmtree(trace_dir, ignore_errors=True)

    t_ref = time.perf_counter()
    ref = reference.Reference(ref_mod, prog.model, cell.config["federation"],
                              cell.traffic)
    theirs = ref.run(lambda: prog.init(keys[0]), keys[1],
                     lambda t: tuple(prog.batch(keys[2], t))[:2], checked)
    gaps = reference.compare(readings, theirs, prog.names)
    print(f"reference: {checked} rounds in {time.perf_counter() - t_ref:.3f}"
          " s", file=sys.stderr)

    # a limit of null: the number has no upper reading in this cell (no
    # control or fault moves it) and is shown, not compared
    checks = {name: {"value": gaps[name][0], "limit": cell.limits[name]}
              for name in ("loss_gap", "step1_gap", "change_gap")
              if cell.limits[name] is not None}
    checks["failed_rounds"] = {"value": failed, "limit": 0}
    for name in ("loss_gap", "step1_gap", "change_gap"):
        if name not in checks:
            print(f"shown, not compared: {name} = {gaps[name][0]!r}",
                  file=sys.stderr)
    correct = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
                  for c in checks.values())

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": hbm}
    if summary is None:
        values = {"tokens_per_s": tokens_per_s,
                  "peak_hbm_gib": hbm / 2 ** 30, "setup_s": setup_s}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    else:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        ctx = Context(cell=cell, trace=summary, compile_s=setup_compile_s,
                      peaks=pk, ref_mod=ref_mod, leaf_shapes=prog.leaf_shapes,
                      tokens_per_s=attempted * prog.tokens_per_round
                      / summary.window_s)
        metrics = {}
        for m in cell.per_layer:
            value = spec.metric_reader(m["name"], bench_dir)(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if summary is not None:
        result["breakdown"] = {"device_ops": summary.top_ops(10),
                               "idle_gaps": summary.idle_gaps(10)}
    result["checks"] = checks
    for name, c in checks.items():
        where = f" (worst: {gaps[name][1]})" if name in gaps else ""
        print(f"check {name} = {c['value']!r} limit {c['limit']!r}{where}",
              file=sys.stderr)
    print(json.dumps(result))
    return 0


class Context:
    """What a per-layer metric's reader sees: the cell (its configuration
    and traffic), the reduced trace, the set-up's compile seconds, the
    chip's peaks, the configuration's reference module (model FLOPs per
    token), the parameter leaves' shapes and the traced window's rate."""

    def __init__(self, **kw):
        self.__dict__.update(kw)
