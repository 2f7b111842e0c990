"""Drive one small cell through the whole harness on the CPU (the look for
a chip skipped) and return its result line."""
from __future__ import annotations

import json
import time

from harness import cli


def run_cell(small_bench, capsys, workload, trace=0, seed=2 ** 33 + 5):
    root, bench = small_bench
    rc = cli.run(["--workload", workload, "--seed", str(seed), "--seconds",
                  "1", "--trace", str(trace)], root, time.perf_counter(),
                 require_chip=False, bench_dir=bench)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])
