"""The reduction from a trace to the per-layer numbers, on a synthetic
trace: stage time split by scope, kernel time summed by name, idle share
as 1 - busy union / window, gaps named by the host span over them."""
from __future__ import annotations

import pytest

from harness import trace
from harness.trace import Op, Span

MS = 1_000_000


def op(name, opcode, start_ms, end_ms, meta="", device="/device:TPU:0"):
    return Op(device, name, opcode, int(start_ms * MS), int(end_ms * MS),
              meta)


@pytest.fixture
def summary():
    ops = [
        # before the window: clipped away
        op("fusion.1", "fusion", -5, -1, "jit(step)/round.local_deltas/dot"),
        op("fusion.2", "fusion", 0, 10, "jit(step)/round.eval_round/dot"),
        # a loop whose body's ops are nested in it: self time 20 - 15
        op("while.3", "while", 10, 30, "jit(step)/round.local_deltas/while"),
        op("fusion.4", "fusion", 10, 25, "jit(step)/round.local_deltas/dot"),
        op("quantize_ef_pack.5", "custom-call", 30, 40,
           "jit(step)/round.encode_reduce/comm.ef_encode/pallas_call"),
        # overlaps the op before it without nesting: busy counts it once
        op("copy.6", "copy", 35, 45, "jit(step)/round.encode_reduce/copy"),
        op("unpack_mma", "custom-call", 50, 55,
           "jit(step)/round.encode_reduce/comm.reduce/pallas_call"),
        # named like a kernel but not a launch of one
        op("unpack_mma_fusion.7", "fusion", 55, 56,
           "jit(step)/round.server_update/add"),
        op("fusion.8", "fusion", 56, 60, "jit(step)/round.server_update/add"),
        op("fusion.9", "fusion", 60, 62, "jit(step)/concatenate"),
        op("fusion.10", "fusion", 62, 63, "jit_perfbench_traffic"),
        # runs past the window's end: clipped to it
        op("fusion.11", "fusion", 95, 120, "jit(step)/round.downlink/add"),
    ]
    spans = [Span("perfbench.window", 0, 100 * MS),
             Span("perfbench.dispatch", 44 * MS, 48 * MS),
             Span("perfbench.wait", 63 * MS, 95 * MS)]
    return trace.Summary(ops, spans, rounds=2)


def test_window_busy_and_idle(summary):
    assert summary.window_s == pytest.approx(0.100)
    # busy: [0, 45] + [50, 63] + [95, 100] = 63 ms
    assert summary.busy_s == pytest.approx(0.063)
    assert summary.idle_share == pytest.approx(1 - 63 / 100)


def test_stage_split_by_scope(summary):
    st = summary.stage_s()
    assert st["round.eval_round"] == pytest.approx(0.010)
    assert st["round.local_deltas"] == pytest.approx(0.020)   # 5 + 15
    assert st["round.encode_reduce"] == pytest.approx(0.025)
    assert st["round.server_update"] == pytest.approx(0.005)
    assert st["round.downlink"] == pytest.approx(0.005)
    assert st[""] == pytest.approx(0.002)
    assert st["traffic"] == pytest.approx(0.001)


def test_kernel_time_summed_by_name(summary):
    assert summary.kernel_s("quantize_ef_pack") == pytest.approx(0.010)
    assert summary.kernel_s("unpack_mma") == pytest.approx(0.005)
    assert summary.kernel_s("block_topk") == 0.0


def test_gaps_named_by_host_span(summary):
    gaps = summary.idle_gaps(3)
    assert gaps[0] == ["perfbench.wait", pytest.approx(0.032)]
    assert gaps[1] == ["perfbench.dispatch", pytest.approx(0.005)]
    top = summary.top_ops(2)
    assert top[0] == ["fusion.4 (fusion) [round.local_deltas]",
                      pytest.approx(0.015)]


def test_busy_is_averaged_over_devices():
    ops = [op("a", "fusion", 0, 10),
           op("b", "fusion", 0, 30, device="/device:TPU:1")]
    s = trace.Summary(ops, [Span("perfbench.window", 0, 100 * MS)], 1)
    assert s.busy_s == pytest.approx(0.020)


def test_union_merges_overlaps():
    assert trace.union([(0, 10), (5, 20), (30, 40), (40, 41)]) == 31
    assert trace.union([]) == 0


def test_event_names_and_hlo_metadata():
    assert trace.parse_event(
        "%unpack_mma.10 = f32[8,64]{1,0} custom-call(f32[2]{0} %a), "
        'custom_call_target="tpu_custom_call"') == ("unpack_mma.10",
                                                     "custom-call")
    assert trace.parse_event(
        "%while.77 = (s32[], f32[2,8]) while((s32[], f32[2,8]) %t), "
        "condition=%c, body=%b") == ("while.77", "while")
    hlo = """HloModule jit_step, is_scheduled=true

%fused_computation.1 (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  ROOT %add.1 = f32[8]{0} add(f32[8]{0} %p0, f32[8]{0} %p0), metadata={op_name="jit(step)/round.server_update/add" source_file="x.py"}
}

ENTRY %main.2 (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  %fusion.3 = f32[8]{0} fusion(f32[8]{0} %a), kind=kLoop, calls=%fused_computation.1
  ROOT %custom-call.4 = f32[8]{0} custom-call(f32[8]{0} %fusion.3), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/round.encode_reduce/pallas_call"}
}
"""
    module, names = trace.op_names(hlo)
    assert module == "jit_step"
    assert names["fusion.3"] == "jit(step)/round.server_update/add"
    assert names["custom-call.4"] == \
        "jit(step)/round.encode_reduce/pallas_call"
