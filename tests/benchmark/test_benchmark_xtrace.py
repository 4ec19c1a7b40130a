"""The reduction from a trace to numbers, on hand-made intervals and on a
small trace recorded on a TPU v5e (``data/train_w1.xplane.pb``:
six steps of a 2-layer, 256-wide trainer, from ``tools/trace_probe.py``)."""

import os

import pytest

from benchmark.lib import xtrace
from benchmark.lib.xtrace import DeviceTrace, Trace

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                       "train_w1.xplane.pb")


def test_union_and_gaps():
    iv = [(0, 10), (5, 20), (30, 40)]
    assert xtrace.union_ns(iv) == 30
    assert xtrace.gaps_ns(iv, 0, 50) == [(20, 30), (40, 50)]
    assert xtrace.gaps_ns(iv, 8, 35) == [(20, 30)]
    assert xtrace.subtract_ns([(0, 10), (30, 40)], [(5, 35)]) == 10


@pytest.mark.parametrize("raw, base", [
    ("%fusion.334 = s32[1,4]{1,0} fusion(s32[4] %x), kind=kLoop", "fusion"),
    ("%all-reduce-start.1 = (f32[8]) all-reduce-start(f32[8] %g)",
     "all-reduce-start"),
    ("flash_fwd", "flash_fwd"),
    ("%copy-done.3 = f32[2] copy-done(...)", "copy-done"),
    ("multiply_add_fusion.12.3", "multiply_add_fusion"),
])
def test_base_name(raw, base):
    assert xtrace.base_name(raw) == base


def _two_devices():
    # device 0: compute 0-100, all-reduce 100-160 of which 120-140 is under
    # a fusion on another line; gap 160-200; compute 200-300
    d0 = DeviceTrace(
        [("%fusion.1 = f32[] fusion()", 0, 100),
         ("%all-reduce.1 = f32[] all-reduce()", 100, 160),
         ("%fusion.2 = f32[] fusion()", 120, 140),
         ("%flash_fwd = f32[] custom-call()", 200, 300)],
        [("jit_step", 0, 160), ("jit_step", 200, 300)])
    # device 1: busy throughout
    d1 = DeviceTrace([("%fusion.1 = f32[] fusion()", 0, 300)],
                     [("jit_step", 0, 150), ("jit_step", 150, 300)])
    spans = [("bench.dispatch", 150, 190, 0), ("bench.fetch", 190, 260, 1),
             ("bench.inner", 195, 199, 2)]
    return Trace({0: d0, 1: d1}, spans)


def test_busy_idle_are_averaged_over_devices():
    t = _two_devices()
    assert t.window == (0, 300)
    assert t.busy_s == pytest.approx((260 + 300) / 2 / 1e9)
    assert t.idle_share == pytest.approx(1 - 280 / 300)


def test_kernel_sums_and_calls():
    t = _two_devices()
    seconds = t.op_seconds()
    assert seconds["fusion"] == pytest.approx((120 + 300) / 2 / 1e9)
    assert seconds["flash_fwd"] == pytest.approx(100 / 2 / 1e9)
    assert t.op_calls("flash_fwd") == [pytest.approx(100 / 1e9)]


def test_exposed_collective_leaves_out_what_compute_hides():
    t = _two_devices()
    # 60 ns of all-reduce on device 0, 20 of them under a fusion; none on 1
    assert t.exposed_collective_s() == pytest.approx(40 / 2 / 1e9)


def test_gaps_go_to_the_span_that_covers_most_of_them():
    t = _two_devices()
    gaps = t.idle_gaps()
    # the one gap, 160-200 on device 0: dispatch covers 30, fetch 10
    assert gaps == {"bench.dispatch": pytest.approx(40 / 2 / 1e9)}
    lonely = Trace({0: DeviceTrace([("a", 0, 10), ("b", 50, 60)], [])}, [])
    assert lonely.idle_gaps() == {
        xtrace.UNATTRIBUTED: pytest.approx(40 / 1e9)}


def test_steady_steps_skips_the_first_executions():
    t = _two_devices()
    s = xtrace.steady_steps(t, 1)
    assert s.window == (150, 300)
    assert xtrace.step_count(s) == 1
    assert xtrace.steady_steps(t, 5).window == t.window


def test_busy_within_spans_by_index():
    t = _two_devices()
    assert t.busy_within_spans("bench.fetch") == [(1, 60)]


def test_world_allreduce_bytes_counts_groups_of_the_whole_world():
    text = '''
    %3 = "stablehlo.all_reduce"(%2) <{channel_handle = #stablehlo.channel_handle<handle = 1, type = 1>, replica_groups = dense<[[0, 1, 2, 3]]> : tensor<1x4xi64>, use_global_device_ids}> ({
    ^bb0(%a: tensor<f32>, %b: tensor<f32>):
      %s = stablehlo.add %a, %b : tensor<f32>
      stablehlo.return %s : tensor<f32>
    }) : (tensor<64x128xf32>) -> tensor<64x128xf32>
    %4 = "stablehlo.all_reduce"(%2) <{replica_groups = dense<[[0, 1], [2, 3]]> : tensor<2x2xi64>}> ({
    ^bb0(%a: tensor<f32>, %b: tensor<f32>):
      stablehlo.return %a : tensor<f32>
    }) : (tensor<8xbf16>) -> tensor<8xbf16>
    '''
    assert xtrace.world_allreduce_bytes(text, 4) == 64 * 128 * 4
    assert xtrace.world_allreduce_bytes(text, 2) == 8 * 2
    assert xtrace.world_allreduce_bytes(text, 1) == 0


def test_recorded_trace_loads_and_reduces():
    t = xtrace.load(FIXTURE)
    assert list(t.devices) == [0]
    assert len(t.devices[0].modules) == 6
    assert {s[0] for s in t.host_spans} == {
        "bench.dispatch", "bench.fetch", "bench.drain"}
    s = xtrace.steady_steps(t, 2)
    assert xtrace.step_count(s) == 4
    assert 0 < s.busy_s < s.window_s
    # 2 layers, forward twice under remat: 4 forward calls a step, 2 of
    # each backward kernel
    assert len(s.op_calls("flash_fwd")) == 16
    assert len(s.op_calls("flash_dq")) == len(s.op_calls("flash_dkv")) == 8
    gaps = s.idle_gaps()
    assert sum(gaps.values()) == pytest.approx(s.window_s - s.busy_s)
    # this probe waited on the host between steps: dispatch covers the gaps
    assert max(gaps, key=gaps.get) == "bench.dispatch"
    assert len(s.breakdown()["device_ops"]) == 10
