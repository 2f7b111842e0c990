"""A small copy of the benchmark for tests on the CPU: the real harness,
readers and references, with cells of small width made only of files.

The tests run from the repository root:

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q perfbench/tests
"""
from __future__ import annotations

import json
import os
import shutil
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

SMALL_DENSE = {
    "name": "small-dense", "family": "dense", "n_layers": 2, "d_model": 64,
    "n_heads": 4, "n_kv_heads": 2, "d_ff": 96, "vocab": 256,
    "tie_embeddings": True, "rope_theta": 10000.0, "norm_eps": 1e-5,
    "remat": True, "param_dtype": "float32"}
SMALL_SSM = {
    "name": "small-ssm", "family": "ssm", "n_layers": 2, "d_model": 64,
    "n_heads": 1, "n_kv_heads": 1, "d_ff": 0, "vocab": 256,
    "tie_embeddings": True, "norm_eps": 1e-5,
    "ssm": {"d_state": 16, "d_conv": 4, "expand": 2, "head_dim": 16,
            "chunk": 16, "n_groups": 1},
    "sub_quadratic": True, "remat": True, "param_dtype": "float32"}


def federation(full_eval: bool):
    return {"strategy": "fedsgm", "lr": 0.03,
            "switch": {"mode": "soft", "eps": 0.0, "beta": 2.0},
            "budget": 6.0, "downlink": "none", "track_wbar": True,
            "participation": "gather", "comm": "pallas",
            "full_eval": full_eval}


def mix(kind: str, clients: int = 2, participating: int = 2,
        local_steps: int = 1):
    up = {"kind": "quant", "bits": 8, "block": 64} if kind == "quant" \
        else {"kind": "topk", "ratio": 0.1, "block": 64}
    return {"why": "small test mix", "clients": clients,
            "participating": participating, "local_steps": local_steps,
            "seq": 32, "batch": 1, "hetero": 0.5, "zipf_a": 1.2,
            "minority_frac": 0.125, "uplink": up}


LIMITS = {"rounds": 3, "loss_gap": 1e-4, "step1_gap": 1e-3,
          "change_gap": 1e-3}

CELLS = {
    "small-dense.quant": ("small-dense", "small.quant"),
    "small-dense.topk": ("small-dense", "small.topk"),
    "small-ssm.quant.n4m2": ("small-ssm", "small.quant.n4m2"),
}


def write(path, obj):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


@pytest.fixture(scope="session")
def small_bench(tmp_path_factory):
    """(root, bench_dir) of a checkout-like tree whose BENCHMARK.json names
    small cells; the harness, readers and references are the real ones."""
    root = str(tmp_path_factory.mktemp("checkout"))
    bench = os.path.join(root, "perfbench")
    for sub in ("harness", "metrics"):
        shutil.copytree(os.path.join(BENCH, sub), os.path.join(bench, sub))
    os.makedirs(os.path.join(bench, "configs"))
    shutil.copy(os.path.join(BENCH, "configs", "smollm-360m.py"),
                os.path.join(bench, "configs", "small-dense.py"))
    shutil.copy(os.path.join(BENCH, "configs", "mamba2-130m.py"),
                os.path.join(bench, "configs", "small-ssm.py"))
    for name, model, full in (("small-dense", SMALL_DENSE, True),
                              ("small-ssm", SMALL_SSM, False)):
        write(os.path.join(bench, "configs", name + ".json"),
              {"source": "test", "model": model, "reduced": [],
               "federation": federation(full)})
    write(os.path.join(bench, "traffic", "small.quant.json"), mix("quant"))
    write(os.path.join(bench, "traffic", "small.topk.json"), mix("topk"))
    write(os.path.join(bench, "traffic", "small.quant.n4m2.json"),
          mix("quant", clients=4, participating=2))
    for cell in CELLS:
        write(os.path.join(bench, "limits", cell + ".json"), LIMITS)
    real = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    write(os.path.join(root, "BENCHMARK.json"), {
        **real,
        "configs": [{"name": c, "source": "test", "reduced": [],
                     "file": f"perfbench/configs/{c}.json", "why": "test"}
                    for c in ("small-dense", "small-ssm")],
        "workloads": [{"name": w, "config": c, "traffic": t, "chips": 1,
                       "why": "test"} for w, (c, t) in CELLS.items()],
        "per_layer": [{k: v for k, v in m.items() if k != "workloads"}
                      for m in real["per_layer"]]})
    return root, bench
