"""Readings that the limits of ``correct`` are set from, for one cell.

    python3 perfbench/calibrate.py --workload <name> --seeds <n> [<n> ...]

For each seed, in one process on the chip and at the cell's own size: the
program's first rounds (the harness's own set-up path), then the plain
float32 reference, the control (the same reference with the model in
bfloat16, the lower precision a later change could be tempted by) and a
planted fault (the reference aggregating half of the participants, the
mean taken over them).  Each of the three is compared with the float32
reference by the harness's own comparison.  Prints one JSON line per seed
and appends them to ``perfbench_runs/calibrate-<workload>.jsonl``.
"""
import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
from harness import boot  # noqa: E402

ROOT = boot.setup(HERE)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp

    from harness import program, reference, spec
    cell = spec.load_cell(ROOT, args.workload)
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("calibration reads the chip: no TPU found")
    ref_mod = spec.load_reference(cell.config["name"])
    prog = program.Program(cell.config, cell.traffic, ref_mod)
    rounds = int(cell.limits["rounds"])
    fed = cell.config["federation"]
    refs = {"reference": reference.Reference(ref_mod, prog.model, fed,
                                             cell.traffic),
            "control": reference.Reference(ref_mod, prog.model, fed,
                                           cell.traffic, dtype=jnp.bfloat16),
            "half": reference.Reference(ref_mod, prog.model, fed,
                                        cell.traffic, fault="half")}
    out_dir = os.path.join(ROOT, "perfbench_runs")
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"calibrate-{cell.name}.jsonl")
    with open(path, "a") as log:
        for seed in args.seeds:
            t0 = time.perf_counter()
            state, keys = prog.state(seed)
            state, mine = prog.first_rounds(state, keys, rounds)
            del state
            got = {name: r.run(lambda: prog.init(keys[0]), keys[1],
                               lambda t: tuple(prog.batch(keys[2], t))[:2],
                               rounds)
                   for name, r in refs.items()}
            base = got.pop("reference")
            row = {"workload": cell.name, "seed": seed, "rounds": rounds,
                   "program": reference.compare(mine, base, prog.names)}
            for name, r in got.items():
                row[name] = reference.compare(r, base, prog.names)
            row["f"] = [float(x) for x in base.f]
            row["g"] = [float(x) for x in base.g]
            row["seconds"] = time.perf_counter() - t0
            line = json.dumps(row)
            print(line, flush=True)
            log.write(line + "\n")
            log.flush()


if __name__ == "__main__":
    main()
