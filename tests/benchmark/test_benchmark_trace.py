"""The trace reduction, on hand-built events and on a small trace
recorded on a TPU v5e (six executions of one jitted step with named
scopes, a ``tpunet/data_wait`` span before each)."""

import os

import pytest

from benchmark import trace_reduce as tr

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "probe_v5e.xplane.pb")


def _trace():
    ops = [("%fusion.1 = f32[8] fusion(...)", 0.0, 1.0),
           ("%custom-call.2 = bf16[8] custom-call(...)", 0.5, 1.0),   # overlaps
           ("%fusion.1 = f32[8] fusion(...)", 3.0, 1.0),
           ("%copy.3 = f32[8] copy(...)", 9.0, 1.0)]
    spans = [("tpunet/data_wait", 1.6, 1.2), ("train", 3.0, 1.0),
             ("tpunet/serve_decode", 4.1, 0.2)]
    return tr.Trace(device_ops={"/device:TPU:0": ops}, host_spans=spans)


def test_busy_is_the_union_of_intervals():
    assert tr.merge_intervals([(3, 4), (0, 1), (0.5, 1.5), (1.5, 2)]) == [
        (0, 2), (3, 4)]
    busy, window = tr.busy_and_window(_trace())
    assert busy == pytest.approx(3.5) and window == pytest.approx(10.0)
    assert tr.idle_pct(_trace()) == pytest.approx(65.0)
    assert tr.idle_pct(_trace(), window_s=7.0) == pytest.approx(50.0)
    assert tr.idle_pct(tr.Trace()) is None


def test_busy_is_averaged_over_devices():
    t = _trace()
    t.device_ops["/device:TPU:1"] = [("%a = f32[] add()", 0.0, 1.5)]
    busy, _ = tr.busy_and_window(t, window_s=10.0)
    assert busy == pytest.approx((3.5 + 1.5) / 2)


def test_pattern_share_and_top_ops():
    t = _trace()
    assert tr.pattern_share_pct(t, ["custom-call"]) == pytest.approx(25.0)
    assert tr.pattern_share_pct(t, ["^%fusion", "copy"]) == pytest.approx(75.0)
    assert tr.pattern_share_pct(tr.Trace(), ["x"]) is None
    assert tr.top_ops(t, 2) == [["fusion.1", 2.0], ["custom-call.2", 1.0]]
    assert tr.short_name("%fusion.1 = f32[8] fusion(...)") == "fusion.1"


def test_gaps_are_attributed_to_the_covering_host_span():
    gaps = tr.idle_gaps(_trace(), 5)
    assert gaps[0] == ["host:other", pytest.approx(5.0)]      # 4.0 .. 9.0
    assert gaps[1] == ["tpunet/data_wait", pytest.approx(1.5)]  # 1.5 .. 3.0
    assert len(gaps) == 2
    out = tr.breakdown(_trace())
    assert set(out) == {"device_ops", "idle_gaps"}
    assert tr.span_durations(_trace(), "train") == [1.0]


def test_recorded_v5e_trace():
    t = tr.load(RECORDED)
    assert list(t.device_ops) == ["/device:TPU:0"]
    ops = t.device_ops["/device:TPU:0"]
    assert len(ops) == 102                         # 6 executions x 17 ops
    busy, window = tr.busy_and_window(t)
    # six executions of ~217 us each, spread over ~55 ms of host sleeps
    assert 6 * 190e-6 < busy < 6 * 220e-6
    assert 0.05 < window < 0.06
    assert 97.0 < tr.idle_pct(t) < 98.5
    waits = tr.span_durations(t, "tpunet/data_wait")
    assert len(waits) == 6 and all(0.010 <= w < 0.012 for w in waits)
    assert len(tr.span_durations(t, "train")) == 6
    top = tr.top_ops(t, 3)
    assert top[0][0] == "convolution_tanh_fusion"
    assert tr.pattern_share_pct(t, ["fusion"]) > 95.0
    gaps = tr.idle_gaps(t, 5)
    assert len(gaps) == 5
    assert all(name == "tpunet/data_wait" and 0.009 < s < 0.012
               for name, s in gaps)
