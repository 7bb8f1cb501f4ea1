"""``run.py`` end to end at a tiny size, for the LM train cell: the
stages run in-process with the look for a chip skipped (off the TPU the
program's own dispatch takes its reference paths). The reference agrees
with the program's float32 path, the float8 control does not, a step
that returns its state unchanged comes out as not correct, and the
command line itself refuses to report anything off the TPU."""

import json
import os
import shutil
import subprocess
import sys

import pytest

import bench_tiny_root
from benchmark import run

CELL = "gpt2-xl.train-b8-t1024"
LIMITS = {"loss_gap_step1": 1e-4, "loss_gap_step2": 1e-4,
          "loss_gap_step3": 1e-4, "grad_norm_gap": 2e-3,
          "delta_norm_gap": 0.3, "grad_norm_gap_global": 1e-3,
          "delta_norm_gap_global": 0.1, "rows_not_in_dataset": 0,
          "nonfinite_window_losses": 0}
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = bench_tiny_root.make(str(tmp_path_factory.mktemp("bench")))
    bench_tiny_root.set_limits(root, CELL, LIMITS)
    return root


@pytest.fixture(scope="module")
def sound(root, tmp_path_factory):
    ctx = bench_tiny_root.context(
        root, CELL, str(tmp_path_factory.mktemp("work")), control="fp8")
    prog = run.run_stage(ctx, "program")
    ref = run.run_stage(ctx, "reference")
    return ctx, prog, ref


def test_reference_agrees_with_the_float32_program(sound):
    _, prog, ref = sound
    assert ref["correct"] is True
    for name, limit in LIMITS.items():
        assert ref["numbers"][name] <= limit, name
    assert prog["numbers"]["rows_not_in_dataset"] == 0
    assert prog["attempted"] >= 4 and prog["failed"] == 0


def test_float8_control_comes_out_not_correct(sound):
    _, _, ref = sound
    low = ref["control"]
    assert low["grad_norm_gap"] > LIMITS["grad_norm_gap"]
    assert low["grad_norm_gap"] > 3 * ref["numbers"]["grad_norm_gap"]


def test_result_line_has_exactly_the_contract_keys(sound):
    ctx, prog, ref = sound
    line = run.final_line(ctx["cell"], False, prog, ref)
    assert set(line) == RESULT_KEYS
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    # a rehearsal reports no timed number under a device metric's name
    assert line["metrics"] == {} and line["device"]["platform"] == "cpu"
    assert all(v is None for v in prog["metrics"].values())
    json.dumps(line)


def test_traced_rehearsal_reads_only_untimed_layer_metrics(root, tmp_path):
    ctx = bench_tiny_root.context(root, CELL, str(tmp_path), trace=1)
    prog = run.run_stage(ctx, "program")
    assert "compiles_in_window.train" in prog["metrics"]
    assert "device_idle_pct.train" not in prog["metrics"]   # no device trace


def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        root, tmp_path, monkeypatch):
    from tpunet.train import loop

    real = loop.make_lm_train_step

    def broken(*a, **k):
        step = real(*a, **k)

        def unchanged(state, x, y, rng):
            _, m = step(state, x, y, rng)
            return state, m
        return unchanged

    monkeypatch.setattr(loop, "make_lm_train_step", broken)
    ctx = bench_tiny_root.context(root, CELL, str(tmp_path))
    run.run_stage(ctx, "program")
    ref = run.run_stage(ctx, "reference")
    assert ref["correct"] is False
    assert ref["numbers"]["delta_norm_gap"] == pytest.approx(1.0, abs=1e-4)
    assert ref["numbers"]["delta_norm_gap_global"] == pytest.approx(
        1.0, abs=1e-4)
    assert ref["numbers"]["grad_norm_gap"] > LIMITS["grad_norm_gap"]


def _cli(cwd, env=None):
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         "5", "--seconds", "1", "--trace", "0"], cwd=cwd, text=True,
        capture_output=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu", **(env or {})})


def test_command_line_off_the_tpu_exits_nonzero_with_no_result():
    out = _cli(bench_tiny_root.REPO)
    assert out.returncode != 0
    assert not [ln for ln in out.stdout.splitlines() if ln.startswith("{")]


def test_command_line_in_a_bare_copy_exits_nonzero_with_no_result(tmp_path):
    """Only BENCHMARK.json and the files under ``paths``: no program."""
    bare = os.path.join(tmp_path, "bare")
    os.makedirs(os.path.join(bare, "tests"))
    shutil.copy(os.path.join(bench_tiny_root.REPO, "BENCHMARK.json"), bare)
    for path in ("benchmark", "tests/benchmark"):
        shutil.copytree(os.path.join(bench_tiny_root.REPO, path),
                        os.path.join(bare, path),
                        ignore=shutil.ignore_patterns("__pycache__"))
    out = _cli(bare, env={"PYTHONPATH": ""})
    assert out.returncode != 0
    assert not [ln for ln in out.stdout.splitlines() if ln.startswith("{")]
