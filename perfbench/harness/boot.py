"""The environment of a benchmark process, set before JAX is imported.

JAX's persistent compilation cache lives in ``<checkout>/.jax_cache`` and
the TPU runtime's logs in ``<checkout>/perfbench_runs/tpu_logs`` (by default
they would go to ``/tmp``), so a run reads and writes only inside its
checkout.  The benchmark's own modules and the program (``src``) go on the
import path.
"""
import os
import sys


def setup(bench_dir: str) -> str:
    """Set the environment for the benchmark in ``bench_dir``; returns the
    checkout's root."""
    root = os.path.dirname(bench_dir)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(root, ".jax_cache")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(root, "perfbench_runs",
                                                      "tpu_logs"))
    os.makedirs(os.environ["TPU_LOG_DIR"], exist_ok=True)
    for p in (os.path.join(root, "src"), bench_dir):
        if p not in sys.path:
            sys.path.insert(0, p)
    return root
