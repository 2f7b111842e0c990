"""Whole runs of small cells on the CPU: the result line's keys, a correct
comparison with the plain reference, and no result without a chip."""
from __future__ import annotations

import time

import pytest
from runs import run_cell

from harness import cli


@pytest.mark.parametrize("workload", ["small-dense.quant", "small-dense.topk",
                                      "small-ssm.quant.n4m2"])
def test_small_cell_is_correct(small_bench, capsys, workload):
    out = run_cell(small_bench, capsys, workload)
    assert out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {"tokens_per_s", "peak_hbm_gib", "setup_s"}
    assert list(out)[-1] == "checks"
    assert set(out["checks"]) == {"loss_gap", "step1_gap", "change_gap",
                                  "failed_rounds"}


def test_traced_run_reports_per_layer_metrics(small_bench, capsys):
    out = run_cell(small_bench, capsys, "small-dense.quant", trace=1)
    assert out["correct"] is True
    assert "compile_s" in out["metrics"]
    assert "tokens_per_s" not in out["metrics"]
    assert {"busy_s", "window_s"} <= set(out["device"])
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


def test_no_result_without_a_chip(small_bench, capsys):
    root, bench = small_bench
    rc = cli.run(["--workload", "small-dense.quant", "--seed", "1",
                  "--seconds", "1"], root, time.perf_counter(),
                 require_chip=True, bench_dir=bench)
    assert rc != 0
    assert capsys.readouterr().out == ""
