"""End-to-end CLI tests: config parsing and a full train.py run."""

import json
import os
import subprocess
import sys

import pytest

from tpunet.config import ModelConfig, config_from_args

REPO = os.path.dirname(os.path.dirname(__file__))


def test_presets_match_reference_batch_sizes():
    assert config_from_args(["--preset", "serial"]).data.batch_size == 64
    assert config_from_args(["--preset", "single"]).data.batch_size == 128
    cfg = config_from_args([])
    assert cfg.epochs == 20 and cfg.seed == 42
    assert cfg.optim.learning_rate == 1e-4
    assert cfg.optim.step_size_epochs == 10 and cfg.optim.gamma == 0.1
    assert cfg.data.image_size == 224


def test_attention_defaults_to_measured_policy():
    """Defaults encode the measured policy (VERDICT round-2 item 8):
    'auto' — the flash kernel on TPU (fastest in every measured regime,
    README long-context table), dense semantics elsewhere. Dense stays
    selectable as the cross-backend reference."""
    assert config_from_args([]).model.attention == "auto"
    assert config_from_args(
        ["--attention", "dense"]).model.attention == "dense"


def test_arg_overrides():
    cfg = config_from_args([
        "--preset", "serial", "--epochs", "2", "--batch-size", "32",
        "--image-size", "64", "--lr", "0.01", "--dataset", "synthetic",
        "--mesh-data", "4", "--dtype", "float32", "--resume",
        "--checkpoint-dir", "/tmp/x"])
    assert cfg.epochs == 2
    assert cfg.data.batch_size == 32 and cfg.data.image_size == 64
    assert cfg.optim.learning_rate == 0.01
    assert cfg.mesh.data == 4
    assert cfg.model.dtype == "float32"
    assert cfg.checkpoint.resume and cfg.checkpoint.directory == "/tmp/x"


def test_round4_flags_parse_and_default():
    cfg = config_from_args([
        "--preset", "serial", "--model", "lm_pp", "--dataset",
        "synthetic_lm", "--moe-experts", "4", "--moe-dispatch",
        "alltoall", "--vocab-ce", "sharded", "--pp-schedule",
        "interleaved", "--pp-virtual", "4"])
    assert cfg.model.moe_dispatch == "alltoall"
    assert cfg.model.vocab_ce == "sharded"
    assert cfg.model.pp_schedule == "interleaved"
    assert cfg.model.pp_virtual == 4
    dflt = config_from_args(["--preset", "serial"])
    assert dflt.model.moe_dispatch == "auto"
    assert dflt.model.vocab_ce == "auto"
    assert dflt.model.pp_virtual == 2


@pytest.mark.slow
def test_train_cli_end_to_end(tmp_path):
    """python train.py on synthetic data: epoch lines in the reference
    format, checkpoints written, exit code 0."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "train.py", "--preset", "distributed",
         "--dataset", "synthetic", "--synthetic-size", "128",
         "--epochs", "2", "--batch-size", "32", "--image-size", "32",
         "--dtype", "float32", "--width-mult", "0.5",
         "--checkpoint-dir", str(tmp_path / "ck")],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=570)
    assert out.returncode == 0, out.stderr[-3000:]
    lines = out.stdout.splitlines()
    epoch_lines = [l for l in lines if l.startswith("Epoch ")]
    assert len(epoch_lines) == 2
    assert "Train Loss:" in epoch_lines[0] and "Test Acc:" in epoch_lines[0]
    assert any(l.startswith("Best test accuracy:") for l in lines)
    assert any(l.startswith("Total training time:") for l in lines)
    assert (tmp_path / "ck" / "state").is_dir()


def test_eval_only_flag_parses():
    cfg = config_from_args(["--eval-only"])
    assert cfg.eval_only


@pytest.mark.slow
def test_eval_only_evaluates_best_checkpoint(tmp_path):
    """--eval-only on a trained dir reproduces the best test accuracy
    without training; on an empty dir it raises cleanly."""
    import dataclasses

    import pytest as _pytest

    from tpunet.config import (CheckpointConfig, DataConfig, MeshConfig,
                               ModelConfig, OptimConfig, TrainConfig)
    from tpunet.train.loop import Trainer

    def cfg(**kw):
        return TrainConfig(
            epochs=1,
            data=DataConfig(dataset="synthetic_lm", batch_size=16,
                            synthetic_train_size=32,
                            synthetic_test_size=16, seq_len=32,
                            vocab_size=32),
            model=ModelConfig(name="lm", vit_hidden=64, vit_depth=2,
                              vit_heads=4, dropout_rate=0.0,
                              dtype="float32", vocab_size=32,
                              max_seq_len=32),
            optim=OptimConfig(learning_rate=3e-3),
            mesh=MeshConfig(),
            checkpoint=CheckpointConfig(directory=str(tmp_path / "ck"),
                                        save_last=False),
            **kw,
        )

    trainer = Trainer(cfg())
    try:
        history = trainer.train()
        trained_acc = history[-1]["test_accuracy"]
    finally:
        trainer.close()

    ev = Trainer(cfg(eval_only=True))
    try:
        m = ev.evaluate_checkpoint()
        assert m["accuracy"] == _pytest.approx(trained_acc, abs=1e-6)
    finally:
        ev.close()

    empty = Trainer(dataclasses.replace(
        cfg(eval_only=True),
        checkpoint=CheckpointConfig(directory=str(tmp_path / "nope"),
                                    save_last=False)))
    try:
        with _pytest.raises(FileNotFoundError, match="no checkpoint"):
            empty.evaluate_checkpoint()
    finally:
        empty.close()


# MobileNetV2 has one train path since PR 45: a launch script or a
# configuration that still names one of its four removed levers fails
# loudly, it does not run another program.
REMOVED_LEVERS = {
    "--pallas-depthwise": "use_pallas_depthwise",
    "--no-pallas-depthwise": "use_pallas_depthwise",
    "--fused-bn": "fused_bn", "--no-fused-bn": "fused_bn",
    "--fused-ir": "fused_ir", "--no-fused-ir": "fused_ir",
    "--block-remat": "block_remat", "--no-block-remat": "block_remat",
}


@pytest.mark.parametrize("spelling", list(REMOVED_LEVERS))
def test_removed_lever_is_refused(spelling, capsys):
    with pytest.raises(SystemExit) as ei:
        config_from_args(["--preset", "serial", spelling])
    assert ei.value.code == 2
    assert f"unrecognized arguments: {spelling}" in capsys.readouterr().err
    field = REMOVED_LEVERS[spelling]
    with pytest.raises(TypeError, match=field):
        ModelConfig(**{field: not spelling.startswith("--no-")})
