"""The attribution of device ops to the program's scopes, on a synthetic
trace: metadata-less ops inherit their enclosing loop's path, each new
per-layer reader reads its part, and the parts partition the busy round."""
from __future__ import annotations

import pytest

from harness import cli, scopes, spec, trace
from harness.trace import Op, Span

MS = 1_000_000
J = "jit(step)/"
LD = J + "round.local_deltas/"
LOOP = LD + "transpose(jvp())/while"
NEW = ("model_fwd_ms", "model_bwd_ms", "mixer_ms", "mlp_ms", "head_loss_ms",
       "local_update_ms", "wire_moves_ms", "delta_norm_ms", "unscoped_ms")


def op(name, opcode, start_ms, end_ms, meta=""):
    return Op("/device:TPU:0", name, opcode, int(start_ms * MS),
              int(end_ms * MS), meta)


def summarize(ops):
    return trace.Summary(ops, [Span("perfbench.window", 0, 100 * MS)],
                         rounds=2)


@pytest.fixture
def summary():
    ev = J + "round.eval_round/"
    return summarize([
        op("fusion.1", "fusion", 0, 10, ev + "jvp(model.embed)/mul"),
        op("fusion.2", "fusion", 10, 20, ev + "jvp(model.mixer)/dot_general"),
        op("fusion.3", "fusion", 20, 26, ev + "jvp(model.mlp)/dot_general"),
        op("fusion.4", "fusion", 26, 30, ev + "jvp(model.head)/dot_general"),
        op("fusion.5", "fusion", 30, 32, ev + "jvp(model.loss)/reduce_sum"),
        # the layer scan's backward loop: self time 28 - 22
        op("while.6", "while", 32, 60, LOOP),
        op("fusion.7", "fusion", 33, 43, LOOP + "/body/closed_call/checkpoint"
           "/rematted_computation/model.mixer/dot_general"),
        op("fusion.8", "fusion", 43, 50,
           LOOP + "/body/closed_call/checkpoint/model.mlp/dot_general"),
        # made by XLA without metadata inside the loop: takes its path
        op("dynamic-update-slice.9", "dynamic-update-slice", 50, 55),
        op("fusion.10", "fusion", 60, 62,
           LD + "transpose(jvp(model.head))/dot_general"),
        op("fusion.11", "fusion", 62, 64, LD + "local.update/sub"),
        op("fusion.12", "fusion", 64, 66,
           LD + "transpose(jvp(flat.unflatten))/pad"),
        op("quantize_ef_pack.13", "custom-call", 66, 70,
           J + "round.encode_reduce/comm.ef_encode/quantize_ef_pack/"
           "pallas_call"),
        op("fusion.14", "fusion", 70, 73,
           J + "round.encode_reduce/comm.ef_encode/comm.relayout/reshape"),
        op("fusion.15", "fusion", 73, 75,
           J + "round.encode_reduce/comm.rows/dynamic_update_slice"),
        op("segment_rows.16", "custom-call", 75, 80,
           J + "engine.delta_norm/kernel.segment_rows[pallas]/segment_rows/"
           "pallas_call"),
        op("fusion.17", "fusion", 80, 81,
           J + "engine.delta_norm/comm.rows/scatter"),
        op("fusion.18", "fusion", 81, 83, J + "round.server_update/sub"),
        op("fusion.19", "fusion", 83, 84,
           J + "engine.bookkeeping/flat.unflatten/slice"),
        # without metadata and outside every loop: stays unscoped
        op("copy.20", "copy", 84, 86),
        op("fusion.21", "fusion", 86, 87, J + "random_split"),
        op("fusion.22", "fusion", 87, 88, "jit_perfbench_traffic"),
        op("fusion.23", "fusion", 88, 89, "jit_other_program"),
    ])


def read(name, summary):
    return spec.metric_reader(name)(cli.Context(trace=summary))


def test_metadata_less_ops_take_their_loop_path(summary):
    got = dict(zip((o.name for o in summary.ops), scopes.paths(summary.ops)))
    assert got["dynamic-update-slice.9"] == LOOP
    assert got["copy.20"] == ""
    assert got["fusion.8"].endswith("model.mlp/dot_general")
    assert scopes.part_of(LOOP) == scopes.Part("local_update")


def test_nested_loops_inherit_the_innermost():
    inner = LOOP + "/body/while"
    s = summarize([op("while.1", "while", 0, 50, LOOP),
                   op("while.2", "while", 10, 40, inner),
                   op("dynamic-update-slice.3", "dynamic-update-slice",
                      20, 30),
                   op("dynamic-update-slice.4", "dynamic-update-slice",
                      40, 45),
                   # the loop's last op, ending 1 ns past it in the trace
                   Op("/device:TPU:0", "dynamic-update-slice.5",
                      "dynamic-update-slice", 49 * MS, 50 * MS + 1, ""),
                   op("copy.6", "copy", 50, 60)])
    assert scopes.paths(s.ops) == [LOOP, inner, inner, LOOP, LOOP, ""]


@pytest.mark.parametrize("name, want", [
    ("model_fwd_ms", 32 / 2), ("model_bwd_ms", (10 + 7 + 2) / 2),
    ("mixer_ms", (10 + 10) / 2), ("mlp_ms", (6 + 7) / 2),
    ("head_loss_ms", (10 + 4 + 2 + 2) / 2),
    ("local_update_ms", (6 + 5 + 2 + 2) / 2),
    ("wire_moves_ms", (3 + 2) / 2), ("delta_norm_ms", (5 + 1) / 2),
    ("unscoped_ms", (2 + 1 + 1) / 2)])
def test_reader(summary, name, want):
    assert read(name, summary) == pytest.approx(want)


def test_model_parts_add_up(summary):
    fwd_bwd = read("model_fwd_ms", summary) + read("model_bwd_ms", summary)
    parts = sum(read(n, summary) for n in ("mixer_ms", "mlp_ms",
                                           "head_loss_ms"))
    assert fwd_bwd == pytest.approx(parts)


def test_parts_partition_the_busy_round(summary):
    split = scopes.split(summary)
    busy_ms = summary.busy_s * 1e3 / summary.rounds
    assert sum(split.values()) == pytest.approx(busy_ms)
    rest = {p.name: v for p, v in split.items()
            if p.name in ("traffic", "wire_rest", "engine_rest")}
    assert rest == pytest.approx({"traffic": 0.5, "wire_rest": 2.0,
                                  "engine_rest": 1.5})
    new = sum(read(n, summary) for n in NEW if n not in (
        "mixer_ms", "mlp_ms", "head_loss_ms"))
    assert new + sum(rest.values()) == pytest.approx(busy_ms)


def test_unscoped_ops_are_named(summary):
    top = scopes.unscoped_ops(summary, 2)
    assert top[0] == ["copy.20 (copy)", pytest.approx(1.0)]
    assert top[1][1] == pytest.approx(0.5)


def test_no_reading_from_a_program_without_scopes():
    s = summarize([op("fusion.1", "fusion", 0, 10,
                      J + "round.local_deltas/dot_general"),
                   op("copy.2", "copy", 10, 20)])
    assert all(read(name, s) is None for name in NEW)
