"""The serve cell at a tiny size, closed and open loop: the served
tokens are the float32 reference's best, a token altered where it is
produced comes out as not correct, and the open loop times from the due
time and reports its lateness."""

import json
import os

import pytest

import bench_tiny_root
from benchmark import harness, run

CELL = "gpt2-xl.serve-closed16"
LIMITS = {"served_logit_gap_max": 1e-3, "tokens_missing": 0,
          "failed_requests": 0}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = bench_tiny_root.make(str(tmp_path_factory.mktemp("bench")))
    bench_tiny_root.set_limits(root, CELL, LIMITS)
    return root


def test_closed_loop_is_correct_and_counts_its_requests(root, tmp_path):
    ctx = bench_tiny_root.context(root, CELL, str(tmp_path), control="fp8")
    prog = run.run_stage(ctx, "program")
    ref = run.run_stage(ctx, "reference")
    assert ref["correct"] is True and ref["compared_tokens"] >= 6
    assert prog["attempted"] > 0 and prog["failed"] == 0
    assert prog["numbers"]["tokens_missing"] == 0
    line = run.final_line(ctx["cell"], False, prog, ref)
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device"}
    assert line["metrics"] == {}                    # nothing timed off the TPU
    assert set(prog["metrics"]) == {"serve_tok_per_s", "itl_p95_ms",
                                    "setup_s"}


def test_a_token_altered_where_it_is_produced_is_not_correct(
        root, tmp_path, monkeypatch):
    from tpunet.serve.scheduler import GenerateRequest

    real = GenerateRequest.push_token

    def altered(self, token):
        return real(self, (int(token) + 1) % 64)

    monkeypatch.setattr(GenerateRequest, "push_token", altered)
    ctx = bench_tiny_root.context(root, CELL, str(tmp_path))
    run.run_stage(ctx, "program")
    ref = run.run_stage(ctx, "reference")
    assert ref["correct"] is False
    assert ref["numbers"]["served_logit_gap_max"] > 1e-3


def test_open_loop_arrives_as_one_data_file(root, tmp_path, capsys):
    path = os.path.join(root, "benchmark", "traffic", "serve-closed16.json")
    with open(path) as f:
        traffic = json.load(f)
    traffic.update(arrival="open", rate=30.0, process="bursty",
                   burst_every=10, burst_len=3, burst_factor=3.0,
                   max_requests=40)
    with open(path, "w") as f:
        json.dump(traffic, f)
    ctx = bench_tiny_root.context(root, CELL, str(tmp_path), seconds=0.8)
    assert ctx["cell"]["traffic"]["arrival"] == "open"
    prog = run.run_stage(ctx, "program")
    ref = run.run_stage(ctx, "reference")
    assert ref["correct"] is True
    assert prog["attempted"] >= 5 and prog["failed"] == 0
    assert "generator lateness" in capsys.readouterr().out


def test_window_metrics_count_only_the_window():
    serve = harness.load_module(
        os.path.join(bench_tiny_root.REPO, "benchmark", "runners",
                     "serve.py"), "bench_serve_runner_test")

    def rec(sent, times, want=None, reason="length"):
        return {"sent": sent, "due": None, "token_t": times,
                "tokens": [1] * len(times), "want": want or len(times),
                "reason": reason, "error": None, "queue_s": 0.001,
                "prefill_s": 0.01, "busy": 2}

    records = [rec(-1.0, [-0.5, 0.5, 1.5]),          # sent before the window
               rec(0.0, [1.0, 2.0, 4.0]),
               rec(5.0, [6.0, 11.0]),                # drained after it closed
               rec(9.0, [], want=4, reason="timeout")]
    inside, failed, done, metrics, host = serve.window_metrics(
        records, 0.0, 10.0, slots=4)
    assert len(inside) == 3 and len(failed) == 1 and len(done) == 2
    assert metrics["serve_tok_per_s"] == pytest.approx(6 / 10.0)
    assert metrics["ttft_p95_ms"] == pytest.approx(1000.0)
    assert metrics["itl_p95_ms"] == pytest.approx(
        harness.percentile([1000.0, 2000.0, 5000.0], 95))
    assert host["slots_busy_pct"] == pytest.approx(50.0)
    assert host["queue_ms"] == pytest.approx(1.0)
