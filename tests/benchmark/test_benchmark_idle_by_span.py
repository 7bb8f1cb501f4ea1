"""``trace_idle_by_span``: the first device's idle seconds by the
innermost host span open in them, on hand-built traces and on the small
trace recorded on a TPU v5e; and the eleven metrics of PR 37 that read
the engine thread's phases and the trainer's epoch edges."""

import os

import pytest

import bench_tiny_root
from benchmark import harness, trace_reduce

CELL = harness.load_cell("gpt2-xl.serve-closed16", bench_tiny_root.REPO)
RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "probe_v5e.xplane.pb")

IDLE_SERVE = ["idle_admit_pct.serve", "idle_prefill_read_pct.serve",
              "idle_prefix_adopt_pct.serve", "idle_publish_pct.serve",
              "idle_decode_dispatch_pct.serve", "idle_no_traffic_pct.serve",
              "idle_host_other_pct.serve"]
IDLE_TRAIN = ["idle_epoch_edge_pct.train", "idle_host_other_pct.train"]
SPAN_MS = ["prefix_adopt_ms.serve", "publish_ms.serve"]


def _spec(metric):
    return harness.load_json("benchmark", "layer_metrics", f"{metric}.json",
                             root=bench_tiny_root.REPO)


def _read(metric, trace=None, window_s=10.0):
    spec = _spec(metric)
    return harness.load_reader(CELL, spec["reader"]).read(
        {"trace": trace, "window_s": window_s}, spec["params"])


def _by_span(trace):
    return harness.load_reader(
        CELL, "trace_idle_by_span").gap_seconds_by_span(trace)


def _op(start, seconds):
    return ("%f = f32[] fusion()", start, seconds)


def _admission():
    """One admission between two decode steps as the engine's thread
    lays it out: device busy 0..1 (step N), 1..3 (the prefill call) and
    from 6 (step N+1); the gaps are 3..6 and 7..7.5."""
    ops = [_op(0.0, 1.0), _op(1.0, 2.0), _op(6.0, 1.0), _op(7.5, 0.5)]
    spans = [("tpunet/serve_admit", 0.2, 0.2),
             ("tpunet/serve_prefill_args", 0.4, 0.1),
             ("tpunet/serve_prefill", 0.5, 2.7),           # 0.5 .. 3.2
             ("tpunet/serve_decode_wait", 0.6, 0.5),       # its children:
             ("tpunet/serve_publish", 1.1, 0.1),           # the drained step
             ("tpunet/serve_prefix_adopt", 3.2, 2.0),      # 3.2 .. 5.2
             ("tpunet/serve_publish", 5.2, 0.3),           # 5.2 .. 5.5
             ("tpunet/serve_decode_args", 5.6, 0.2),       # 5.6 .. 5.8
             ("tpunet/serve_decode", 5.8, 1.9),            # 5.8 .. 7.7
             ("tpunet/serve_decode_wait", 7.0, 0.4),       # child, 7.0 .. 7.4
             ("tpunet/serve_publish", 7.4, 0.2)]           # child, 7.4 .. 7.6
    return trace_reduce.Trace(device_ops={"/device:TPU:0": ops},
                              host_spans=spans)


@pytest.mark.parametrize("metric", IDLE_SERVE + IDLE_TRAIN + SPAN_MS)
def test_the_new_metrics_read_nothing_without_a_trace(metric):
    spec = _spec(metric)
    assert spec["name"] == metric
    named = spec["params"].get("spans") or [spec["params"].get("span")]
    for span in named:              # each metric names its spans
        assert span is None or span in spec["what"]
    assert _read(metric) is None
    assert _read(metric, trace_reduce.Trace()) is None


def test_a_child_takes_its_part_of_a_parents_gap():
    given = _by_span(_admission())
    # gap 3..6: the prefill span's tail, the adoption, the publish, a
    # sliver under nothing, the next step's arguments and dispatch
    assert given["tpunet/serve_prefill"] == pytest.approx(0.2)
    assert given["tpunet/serve_prefix_adopt"] == pytest.approx(2.0)
    assert given["tpunet/serve_decode_args"] == pytest.approx(0.2)
    # gap 7..7.5 lies inside serve_decode: its children take 7.0..7.4
    # and 7.4..7.5, the parent keeps 5.8..6.0 of the first gap only
    assert given["tpunet/serve_decode"] == pytest.approx(0.2)
    assert given["tpunet/serve_decode_wait"] == pytest.approx(0.4)
    assert given["tpunet/serve_publish"] == pytest.approx(0.3 + 0.1)
    assert given[None] == pytest.approx(0.1)             # 5.5 .. 5.6
    assert "tpunet/serve_admit" not in given             # the device was busy


def test_the_lines_of_a_partition_add_up_to_the_gaps():
    trace = _admission()
    lines = {m: _read(m, trace) for m in IDLE_SERVE}
    assert lines["idle_prefix_adopt_pct.serve"] == pytest.approx(20.0)
    assert lines["idle_prefill_read_pct.serve"] == pytest.approx(2.0)
    assert lines["idle_publish_pct.serve"] == pytest.approx(4.0)
    assert lines["idle_decode_dispatch_pct.serve"] == pytest.approx(8.0)
    assert lines["idle_admit_pct.serve"] == 0.0
    assert lines["idle_no_traffic_pct.serve"] == 0.0     # no such span: 0
    assert lines["idle_host_other_pct.serve"] == pytest.approx(1.0)
    assert sum(lines.values()) == pytest.approx(35.0)    # 3.5 s of 10
    # the device's idle share is the gaps and the window's two edges
    assert trace_reduce.idle_pct(trace, 10.0) == pytest.approx(
        35.0 + 100.0 * (10.0 - 8.0) / 10.0)


def test_null_is_what_no_span_covers_and_the_window_scales_it():
    ops = [_op(0.0, 1.0), _op(5.0, 1.0)]
    trace = trace_reduce.Trace(device_ops={"/device:TPU:0": ops},
                               host_spans=[("tpunet/serve_idle", 2.0, 1.0)])
    assert _read("idle_host_other_pct.serve", trace) == pytest.approx(30.0)
    assert _read("idle_no_traffic_pct.serve", trace) == pytest.approx(10.0)
    assert _read("idle_no_traffic_pct.serve", trace, 20.0) == \
        pytest.approx(5.0)
    assert _read("idle_no_traffic_pct.serve", trace, 0.0) is None
    # spans of another name take nothing from these lines
    assert _read("idle_epoch_edge_pct.train", trace) == 0.0


def test_of_two_covering_spans_the_one_that_started_last_wins():
    ops = [_op(0.0, 1.0), _op(4.0, 1.0)]
    spans = [("train", 0.5, 4.0),                          # 0.5 .. 4.5
             ("tpunet/train_summarize", 1.5, 1.0),         # 1.5 .. 2.5
             ("tpunet/train_epoch_start", 3.0, 0.5)]       # 3.0 .. 3.5
    trace = trace_reduce.Trace(device_ops={"/device:TPU:0": ops,
                                           "/device:TPU:1": []},
                               host_spans=spans)
    given = _by_span(trace)
    assert given["train"] == pytest.approx(1.5)
    assert given[None] == 0.0
    assert _read("idle_epoch_edge_pct.train", trace) == pytest.approx(15.0)
    assert _read("idle_host_other_pct.train", trace) == 0.0


def test_span_medians_of_the_two_new_phases():
    trace = _admission()
    assert _read("prefix_adopt_ms.serve", trace) == pytest.approx(2000.0)
    assert _read("publish_ms.serve", trace) == pytest.approx(200.0)
    bare = trace_reduce.Trace(device_ops=trace.device_ops, host_spans=[])
    assert _read("prefix_adopt_ms.serve", bare) is None  # as on the parent


def test_on_the_recorded_trace_the_lines_add_up_to_its_gaps():
    trace = trace_reduce.load(RECORDED)
    given = _by_span(trace)
    ops = trace.device_ops[sorted(trace.device_ops)[0]]
    merged = trace_reduce.merge_intervals((s, s + d) for _, s, d in ops)
    gaps = sum(b[0] - a[1] for a, b in zip(merged, merged[1:]))
    assert gaps > 0
    assert sum(given.values()) == pytest.approx(gaps)
    assert set(given) <= {None, "tpunet/data_wait", "train"}
    window = merged[-1][1] - merged[0][0]
    assert _read("idle_host_other_pct.train", trace, window) == \
        pytest.approx(100.0 * given[None] / window)
