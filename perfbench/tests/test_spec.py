"""The benchmark is driven by data: every cell's files load, and a file
added for a new cell is found by its name with no edit."""
from __future__ import annotations

import json
import os
import re
import shutil

import pytest
from conftest import BENCH, ROOT

from harness import spec

BENCHMARK = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("workload",
                         [w["name"] for w in BENCHMARK["workloads"]])
def test_every_cell_loads(workload):
    cell = spec.load_cell(ROOT, workload)
    assert cell.config["model"]["n_layers"] > 0
    assert cell.traffic["participating"] <= cell.traffic["clients"]
    assert {"rounds", "loss_gap", "step1_gap", "change_gap"} <= set(
        cell.limits)
    ref = spec.load_reference(cell.config["name"])
    for fn in ("init", "loss_pair", "flops_per_token"):
        assert callable(getattr(ref, fn))
    names = {m["name"] for m in cell.end_to_end}
    assert {"setup_s", "tokens_per_s"} <= names
    for m in cell.per_layer:
        assert callable(spec.metric_reader(m["name"]))


def test_benchmark_file_keeps_its_contract():
    b = BENCHMARK
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= b["run_seconds"] <= 51
    configs = {c["name"] for c in b["configs"]}
    e2e = {m["name"] for m in b["end_to_end"]}
    cells = {w["name"] for w in b["workloads"]}
    assert "setup_s" in e2e
    for c in b["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        assert c["file"].startswith("perfbench/")
    for w in b["workloads"]:
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in b["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", cells)) <= cells
    for m in b["end_to_end"] + b["per_layer"] + b["workloads"] \
            + b["configs"]:
        assert NAME.match(m["name"]), m["name"]
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "layer", "moves", "workloads", "config",
                          "traffic", "chips", "why", "file", "reduced"}


def test_new_traffic_file_is_found_without_an_edit(tmp_path):
    bench = tmp_path / "perfbench"
    shutil.copytree(os.path.join(BENCH, "traffic"), bench / "traffic")
    new = dict(json.load(open(os.path.join(BENCH, "traffic",
                                           "quant8.e1.json"))))
    new["uplink"] = {"kind": "quant", "bits": 4, "block": 1024}
    (bench / "traffic" / "quant4.e1.json").write_text(json.dumps(new))
    got = spec.load_traffic("quant4.e1", str(bench))
    assert got["uplink"]["bits"] == 4 and got["name"] == "quant4.e1"


def test_new_cell_is_found_without_an_edit(small_bench):
    root, bench = small_bench
    cell = spec.load_cell(root, "small-dense.topk", bench)
    assert cell.traffic["uplink"]["kind"] == "topk"
    assert cell.config["model"]["d_model"] == 64
    kernels = {m["name"] for m in cell.per_layer}
    assert "mfu" in kernels
