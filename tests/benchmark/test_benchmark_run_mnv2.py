"""The MobileNetV2 train cell at a tiny size: the plain reference (its
own convolutions, BatchNorm, augmentation copy and dropout draw) follows
the program's float32 path through three steps, and the float8 control
comes out as not correct."""

import pytest

import bench_tiny_root
from benchmark import run

CELL = "mnv2-224.train-b128"
LIMITS = {"loss_gap_step1": 1e-3, "loss_gap_step2": 1e-2,
          "loss_gap_step3": 1e-2, "grad_norm_gap": 2e-2,
          "delta_norm_gap": 0.5, "grad_norm_gap_global": 1e-2,
          "delta_norm_gap_global": 0.1, "bn_var_gap_shallow": 1e-3,
          "rows_not_in_dataset": 0,
          "nonfinite_window_losses": 0}


@pytest.fixture(scope="module")
def sound(tmp_path_factory):
    root = bench_tiny_root.make(str(tmp_path_factory.mktemp("bench")))
    bench_tiny_root.set_limits(root, CELL, LIMITS)
    ctx = bench_tiny_root.context(
        root, CELL, str(tmp_path_factory.mktemp("work")), control="fp8")
    prog = run.run_stage(ctx, "program")
    return prog, run.run_stage(ctx, "reference")


def test_reference_follows_the_float32_program(sound):
    prog, ref = sound
    assert ref["correct"] is True
    # augmentation and dropout are drawn as the program draws them: the
    # first loss agrees to float32 rounding, not to a distribution
    assert ref["numbers"]["loss_gap_step1"] < 1e-4
    assert prog["numbers"]["rows_not_in_dataset"] == 0


def test_float8_control_comes_out_not_correct(sound):
    _, ref = sound
    low, got = ref["control"], ref["numbers"]
    assert low["grad_norm_gap"] > LIMITS["grad_norm_gap"]
    assert low["grad_norm_gap"] > 3 * got["grad_norm_gap"]
    assert low["loss_gap_step1"] > 3 * got["loss_gap_step1"]
    assert low["bn_var_gap_shallow"] > LIMITS["bn_var_gap_shallow"]
    assert low["bn_var_gap_shallow"] > 10 * got["bn_var_gap_shallow"]
