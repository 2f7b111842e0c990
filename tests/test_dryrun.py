"""Dry-run machinery tests.

Full production-mesh lowering runs in subprocesses (device count locks at
first jax init -- one representative case here; the full 10x4x2 sweep is
results/dryrun.jsonl, summarized in EXPERIMENTS.md).  Roofline HLO parsing
is tested in-process on a toy sharded program.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_roofline_hlo_parsing():
    from repro.launch import roofline
    hlo = """
ENTRY %main (a: f32[128,64]) -> f32[128,64] {
  %x = f32[128,64]{1,0} parameter(0)
  %ar = f32[128,64]{1,0} all-reduce(f32[128,64]{1,0} %x), replica_groups={}
  %ag = f32[256,64]{1,0} all-gather(f32[128,64]{1,0} %ar), dimensions={0}
}
%body_1 (p: f32[8]) -> f32[8] {
  %y = f32[8]{0} parameter(0)
  %ar2 = f32[8]{0} all-reduce(f32[8]{0} %y)
}
"""
    out = roofline.collective_bytes(hlo)
    assert out["all-reduce"] == 128 * 64 * 4 + 8 * 4
    assert out["all-gather"] == 128 * 64 * 4
    assert out["in_loop"] == 8 * 4
    corrected = roofline.corrected_collective_bytes(out, 10)
    assert corrected == out["total"] + 9 * 8 * 4


def test_roofline_terms():
    from repro.launch import roofline
    t = roofline.roofline_terms(197e12, 0.0, 0.0, 256, "TPU v5 lite")
    assert abs(t["compute_s"] - 1.0) < 1e-9
    assert t["dominant"] == "compute"
    t = roofline.roofline_terms(0.0, 819e9, 50e9 * 2, 256, "TPU v5 lite")
    assert t["dominant"] == "collective"


def test_roofline_unknown_device_kind_raises():
    import pytest
    from repro.launch import roofline
    with pytest.raises(KeyError, match="no published peaks"):
        roofline.roofline_terms(1.0, 1.0, 1.0, 1, "cpu")


def test_model_flops():
    from repro import configs
    from repro.launch import roofline
    dense = configs.get_config("qwen3-4b")
    moe = configs.get_config("deepseek-v3-671b")
    assert roofline.model_flops(dense, 1000) == 6.0 * dense.n_params() * 1000
    assert moe.n_active_params() < 0.2 * moe.n_params()


def test_skip_reasons():
    from repro.launch import steps
    assert steps.skip_reason("qwen3-4b", "long_500k") is not None
    assert steps.skip_reason("mamba2-130m", "long_500k") is None
    assert steps.skip_reason("gemma3-4b", "long_500k") is None
    assert steps.skip_reason("qwen3-4b", "train_4k") is None


def test_fed_config_policy():
    """Giants get pod-clients + unidirectional compression (DESIGN.md §5)."""
    from repro import configs as _c
    from repro.launch.steps import GIANTS, fed_config_for

    class FakeMesh:
        axis_names = ("pod", "data", "model")
        class devices:
            shape = (2, 16, 16)
    cfg = _c.get_config("deepseek-v3-671b")
    fed = fed_config_for(cfg, FakeMesh())
    assert fed.client_axis == "pod" and fed.n_clients == 2
    assert fed.downlink.kind == "none"
    small = fed_config_for(_c.get_config("smollm-360m"), FakeMesh())
    assert small.client_axis == "data" and small.n_clients == 16


@pytest.mark.slow
def test_dryrun_subprocess_small_arch():
    """One real lower+compile on the production mesh (256 fake devices)."""
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "smollm-360m",
         "--shape", "decode_32k", "--mesh", "single"],
        capture_output=True, text=True, timeout=900, env=env)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    assert "memory_analysis" in out.stdout
    assert "roofline" in out.stdout


def test_sweep_results_all_lower():
    """Every (arch x shape x mesh) in the recorded sweep is ok or a
    documented skip -- the multi-pod dry-run deliverable."""
    path = os.path.join(os.path.dirname(__file__), "..", "results",
                        "dryrun.jsonl")
    if not os.path.exists(path):
        pytest.skip("sweep not yet recorded")
    latest = {}
    for line in open(path):
        r = json.loads(line)
        latest[(r["arch"], r["shape"], r["mesh"],
                r.get("comm", "dense"), r.get("local_steps", 1))] = r
    base = {k: v for k, v in latest.items()
            if k[3] == "dense" and k[4] == 1}
    assert len(base) >= 70  # 10 archs x 4 shapes x 2 meshes (few reruns)
    bad = {k: v.get("error") for k, v in base.items()
           if v["status"] not in ("ok", "skip")}
    assert not bad, bad
