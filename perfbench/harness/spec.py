"""Find a cell's files by the names in ``BENCHMARK.json``.

Everything that belongs to one configuration, one traffic mix, one cell's
correctness limits or one per-layer metric sits in a file of its own under
``perfbench/``, named after it:

* ``configs/<config>.json``   -- the configuration as it is run, with its
  published source and the federated settings; ``configs/<config>.py`` is
  its plain reference (init, loss, model FLOPs per token),
* ``traffic/<traffic>.json``  -- the round's mix: clients, participants,
  local steps, sequence length, batch, token statistics, uplink compressor,
* ``limits/<workload>.json``  -- the limit of each number that decides
  ``correct`` in that cell,
* ``metrics/<metric>.py``     -- the reader of one per-layer metric.

Adding a cell, a configuration or a metric adds files and entries; no file
here changes.
"""
from __future__ import annotations

import importlib.util
import json
import os
from typing import NamedTuple

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class Cell(NamedTuple):
    name: str
    chips: int
    config: dict            # configs/<config>.json, plus "name"
    traffic: dict           # traffic/<traffic>.json, plus "name"
    limits: dict            # limits/<workload>.json
    end_to_end: list        # BENCHMARK.json metric entries this cell reports
    per_layer: list


def read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """Import a file by path (file names carry ``-`` and ``.``)."""
    spec = importlib.util.spec_from_file_location(
        "perfbench_" + name.replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reference(name: str, bench_dir: str = BENCH_DIR):
    """The configuration's plain reference module (``configs/<name>.py``)."""
    return load_module(os.path.join(bench_dir, "configs", name + ".py"),
                       name)


def load_traffic(name: str, bench_dir: str = BENCH_DIR) -> dict:
    t = read_json(os.path.join(bench_dir, "traffic", name + ".json"))
    t["name"] = name
    return t


def load_limits(workload: str, bench_dir: str = BENCH_DIR) -> dict:
    return read_json(os.path.join(bench_dir, "limits", workload + ".json"))


def metric_reader(name: str, bench_dir: str = BENCH_DIR):
    """``read(ctx) -> float | None`` of one per-layer metric."""
    return load_module(os.path.join(bench_dir, "metrics", name + ".py"),
                       name).read


def applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(root: str, workload: str, bench_dir: str = BENCH_DIR) -> Cell:
    """The cell named ``workload`` in ``<root>/BENCHMARK.json``."""
    bench = read_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"known: {sorted(cells)}")
    w = cells[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = read_json(os.path.join(root, cfg_entry["file"]))
    config["name"] = w["config"]
    return Cell(
        name=workload, chips=int(w["chips"]), config=config,
        traffic=load_traffic(w["traffic"], bench_dir),
        limits=load_limits(workload, bench_dir),
        end_to_end=[m for m in bench["end_to_end"] if applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if applies(m, workload)])
