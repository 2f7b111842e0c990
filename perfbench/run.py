"""Run one cell of the FedSGM round benchmark on the chip.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

from the root of a checkout.  ``BENCHMARK.json`` names the cells; the
harness is in ``perfbench/harness`` and everything that belongs to one
configuration, traffic mix, cell or metric sits in a file of its own under
``perfbench/`` (see ``harness/spec.py``).  JAX's persistent compilation
cache lives in ``<checkout>/.jax_cache``.  Exits non-zero, with no result
line, where JAX finds no TPU or fewer chips than the cell asks for.
"""
import os
import sys
import time

T0 = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
from harness import boot  # noqa: E402

ROOT = boot.setup(HERE)
from harness import cli  # noqa: E402

if __name__ == "__main__":
    sys.exit(cli.run(sys.argv[1:], ROOT, T0))
