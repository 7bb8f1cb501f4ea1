"""Observability: the tpunet/obs/ subsystem (metrics registry, stall
accounting, windowed profiling, sinks, the disabled-path guarantees),
per-step logging (the log_every_steps knob), host-side LR lookup, and
the non-finite-loss guard (SURVEY.md section 5: the reference has none
of these — stdout epoch lines are its only observability and a NaN run
would burn its full walltime)."""

import glob
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpunet.config import (CheckpointConfig, DataConfig, MeshConfig,
                           ModelConfig, ObsConfig, OptimConfig,
                           TrainConfig)
from tpunet.obs import MemorySink
from tpunet.obs.registry import Histogram
from tpunet.train.loop import Trainer
from tpunet.utils.logging import MetricsLogger
from tpunet.utils.timing import Timer

LM_CFG = ModelConfig(name="lm", vit_hidden=64, vit_depth=2, vit_heads=4,
                     dropout_rate=0.0, dtype="float32", vocab_size=32,
                     max_seq_len=64)


def _cfg(**kw):
    kw.setdefault("epochs", 1)
    kw.setdefault("checkpoint",
                  CheckpointConfig(save_best=False, save_last=False))
    return TrainConfig(
        data=DataConfig(dataset="synthetic_lm", batch_size=16,
                        synthetic_train_size=64, synthetic_test_size=16,
                        seq_len=64, vocab_size=32),
        model=LM_CFG,
        optim=OptimConfig(learning_rate=3e-3),
        mesh=MeshConfig(),
        **kw,
    )


def test_log_every_steps_emits_step_lines(capsys):
    trainer = Trainer(_cfg(log_every_steps=2))
    try:
        trainer.train_one_epoch(1)  # 4 steps -> lines at steps 2 and 4
    finally:
        trainer.close()
    lines = [l for l in capsys.readouterr().out.splitlines()
             if l.strip().startswith("step ")]
    assert len(lines) == 2
    assert "loss" in lines[0] and "lr 3.000e-03" in lines[0]
    assert lines[1].strip().startswith("step 4")


def test_default_logs_no_step_lines(capsys):
    trainer = Trainer(_cfg())
    try:
        trainer.train_one_epoch(1)
    finally:
        trainer.close()
    assert "step " not in capsys.readouterr().out


def test_step_line_prints_the_lr_that_produced_the_loss(capsys):
    """optax consumes the PRE-increment count: the first step runs at
    schedule(0), so with a 4-step warmup its line must show lr 0."""
    import dataclasses
    cfg = _cfg(epochs=2, log_every_steps=1)
    cfg = cfg.replace(optim=dataclasses.replace(
        cfg.optim, schedule="constant", warmup_epochs=1.0))
    trainer = Trainer(cfg)
    try:
        trainer.train_one_epoch(1)
    finally:
        trainer.close()
    lines = [l.split() for l in capsys.readouterr().out.splitlines()
             if l.strip().startswith("step ")]
    assert lines[0][-1] == "0.000e+00"          # schedule(0)
    assert lines[3][-1] == "2.250e-03"          # schedule(3) = 3/4 ramp


def test_current_lr_follows_schedule():
    import dataclasses
    cfg = _cfg(epochs=2)
    cfg = cfg.replace(optim=dataclasses.replace(
        cfg.optim, schedule="constant", warmup_epochs=1.0))
    trainer = Trainer(cfg)  # 4 steps/epoch; warmup spans epoch 1
    try:
        assert trainer.current_lr() == pytest.approx(0.0)
        trainer.train_one_epoch(1)
        # after 4 of 4 warmup steps the ramp is complete
        assert trainer.current_lr() == pytest.approx(3e-3)
    finally:
        trainer.close()


def test_negative_log_every_steps_raises():
    with pytest.raises(ValueError, match="log_every_steps"):
        Trainer(_cfg(log_every_steps=-1))


# ---------------------------------------------------------------------------
# tpunet/obs/: registry, stall accounting, windowed profiling, sinks
# ---------------------------------------------------------------------------


def test_histogram_percentiles_exact():
    h = Histogram()
    for v in range(1, 101):        # 1..100
        h.observe(float(v))
    assert h.percentile(50) == pytest.approx(50.5)
    assert h.percentile(90) == pytest.approx(90.1)
    assert h.percentile(99) == pytest.approx(99.01)
    assert h.percentile(0) == 1.0 and h.percentile(100) == 100.0
    s = h.summary()
    assert s["count"] == 100 and s["mean"] == pytest.approx(50.5)
    h.reset()
    assert h.percentile(50) is None and h.summary() == {}


def test_histogram_single_observation():
    h = Histogram()
    h.observe(3.0)
    assert h.percentile(50) == 3.0 and h.percentile(99) == 3.0


def test_timer_lap_is_monotonic_and_independent_of_elapsed():
    t = Timer()
    first = t.lap()
    time.sleep(0.01)
    second = t.lap()
    assert first >= 0.0 and second >= 0.01
    # elapsed() spans construction -> now, not the last lap
    assert t.elapsed() >= second


def test_registry_snapshot_flattens_instruments():
    from tpunet.obs import Registry
    reg = Registry()
    reg.counter("saves").inc()
    reg.counter("saves").inc(2.0)
    reg.gauge("mem").set(7)
    for v in (1.0, 2.0, 3.0):
        reg.histogram("lap").observe(v)
    snap = reg.snapshot()
    assert snap["saves"] == 3.0
    assert snap["mem"] == 7.0
    assert snap["lap_count"] == 3 and snap["lap_p50"] == 2.0
    reg.reset_window()               # histograms clear, the rest persist
    snap = reg.snapshot()
    assert "lap_p50" not in snap and snap["saves"] == 3.0


def test_memory_sink_receives_epoch_record_with_schema(tmp_path):
    trainer = Trainer(_cfg(checkpoint=CheckpointConfig(
        directory=str(tmp_path), save_best=False, save_last=False)))
    mem = MemorySink()
    trainer.obs.add_sink(mem)
    try:
        trainer.train()
    finally:
        trainer.close()
    recs = mem.by_kind("obs_epoch")
    assert len(recs) == 1
    r = recs[0]
    assert r["epoch"] == 1 and r["steps"] == 4
    assert r["unit"] == "tokens" and r["tokens_per_sec"] > 0
    for k in ("step_time_p50_s", "step_time_p90_s", "step_time_p99_s"):
        assert r[k] > 0
    assert r["step_time_p50_s"] <= r["step_time_p99_s"]
    assert r["input_stall_s"] >= 0 and 0 <= r["stall_frac"] <= 1
    assert isinstance(r["device_memory"], list) and r["device_memory"]
    assert r["live_processes"] == 1
    # ... and the same record landed in metrics.jsonl via the JsonlSink
    on_disk = MetricsLogger.read_records(str(tmp_path / "metrics.jsonl"))
    assert [x for x in on_disk if x.get("kind") == "obs_epoch"]


def test_stall_accounting_sees_slow_input_pipeline(tmp_path):
    trainer = Trainer(_cfg(checkpoint=CheckpointConfig(
        directory=str(tmp_path), save_best=False, save_last=False)))
    mem = MemorySink()
    trainer.obs.add_sink(mem)
    orig = trainer._epoch_batches

    def slow_batches(epoch):
        for batch in orig(epoch):
            time.sleep(0.03)       # fake host-input stall per fetch
            yield batch

    trainer._epoch_batches = slow_batches
    try:
        trainer.train()
    finally:
        trainer.close()
    r = mem.by_kind("obs_epoch")[0]
    assert r["input_stall_s"] >= 0.10    # 4 steps x 30ms, minus slack
    assert r["stall_frac"] > 0


def test_per_step_records_are_opt_in(tmp_path):
    cfg = _cfg(checkpoint=CheckpointConfig(
        directory=str(tmp_path), save_best=False, save_last=False),
        obs=ObsConfig(step_records_every=2))
    trainer = Trainer(cfg)
    mem = MemorySink()
    trainer.obs.add_sink(mem)
    try:
        trainer.train()
    finally:
        trainer.close()
    steps = mem.by_kind("obs_step")
    assert [r["step"] for r in steps] == [0, 2]
    assert all(r["step_time_s"] > 0 for r in steps)


def test_default_path_no_step_records_and_no_device_sync(tmp_path,
                                                         monkeypatch):
    """The zero-overhead contract: at default obs config the loop emits
    per-EPOCH records only and never calls block_until_ready inside the
    step loop (window-edge fences belong to profiling, which is off)."""
    trainer = Trainer(_cfg(checkpoint=CheckpointConfig(
        directory=str(tmp_path), save_best=False, save_last=False)))
    calls = []
    real = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: (calls.append(1), real(x))[1])
    try:
        trainer.train()
    finally:
        trainer.close()
    assert calls == []
    records = MetricsLogger.read_records(str(tmp_path / "metrics.jsonl"))
    assert not [r for r in records if r.get("kind") == "obs_step"]
    assert [r for r in records if r.get("kind") == "obs_epoch"]


def test_no_obs_disables_all_records(tmp_path):
    trainer = Trainer(_cfg(checkpoint=CheckpointConfig(
        directory=str(tmp_path), save_best=False, save_last=False),
        obs=ObsConfig(enabled=False)))
    mem = MemorySink()
    trainer.obs.add_sink(mem)
    try:
        trainer.train()
    finally:
        trainer.close()
    assert mem.records == []
    records = MetricsLogger.read_records(str(tmp_path / "metrics.jsonl"))
    assert not [r for r in records if "kind" in r]
    assert len(records) == 1     # the plain epoch record still logs


def test_windowed_profiling_captures_only_the_window(tmp_path):
    trace_dir = str(tmp_path / "trace")
    cfg = _cfg(checkpoint=CheckpointConfig(
        directory=str(tmp_path / "ck"), save_best=False,
        save_last=False),
        obs=ObsConfig(profile_start_step=1, profile_num_steps=2))
    cfg = cfg.replace(profile_dir=trace_dir)
    trainer = Trainer(cfg)
    try:
        trainer.train_one_epoch(1)   # 4 steps; window = steps [1, 3)
        assert not trainer.obs.profiler.running   # closed at step 3
        assert not glob.glob(os.path.join(trace_dir, "*.hlo.txt"))
    finally:
        trainer.close()
    assert os.path.isdir(trace_dir)
    # the end of the run leaves the step's text beside the trace, for
    # scripts/obs_report.py --trace (tpunet/obs/device_time.py)
    (text,) = glob.glob(os.path.join(trace_dir, "*.hlo.txt"))
    with open(text) as f:
        assert "tpunet_fwd_bwd" in f.read()


def test_window_ending_at_epoch_boundary_closes_at_the_edge(tmp_path):
    """A window whose end coincides with the epoch's last step must
    stop inside the epoch, not bleed across eval/checkpoint into the
    next epoch's first step."""
    trace_dir = str(tmp_path / "trace")
    cfg = _cfg(checkpoint=CheckpointConfig(
        directory=str(tmp_path / "ck"), save_best=False,
        save_last=False),
        obs=ObsConfig(profile_start_step=2, profile_num_steps=2))
    cfg = cfg.replace(profile_dir=trace_dir)
    trainer = Trainer(cfg)
    try:
        trainer.train_one_epoch(1)   # 4 steps; window = steps [2, 4)
        assert not trainer.obs.profiler.running
    finally:
        trainer.close()
    assert os.path.isdir(trace_dir)


def test_windowed_profiling_outside_window_creates_nothing(tmp_path):
    trace_dir = str(tmp_path / "trace")
    cfg = _cfg(checkpoint=CheckpointConfig(
        directory=str(tmp_path / "ck"), save_best=False,
        save_last=False),
        obs=ObsConfig(profile_start_step=100, profile_num_steps=2))
    cfg = cfg.replace(profile_dir=trace_dir)
    trainer = Trainer(cfg)
    try:
        trainer.train_one_epoch(1)
    finally:
        trainer.close()
    assert not os.path.exists(trace_dir)


def test_obs_validation_raises():
    with pytest.raises(ValueError, match="step_records_every"):
        Trainer(_cfg(obs=ObsConfig(step_records_every=-1)))
    with pytest.raises(ValueError, match="profile window"):
        Trainer(_cfg(obs=ObsConfig(profile_num_steps=-1)))


def test_read_records_tolerates_truncated_trailing_line(tmp_path):
    p = tmp_path / "metrics.jsonl"
    p.write_text('{"epoch": 1, "seconds": 2.0}\n'
                 '{"epoch": 2, "seconds": 2.1}\n'
                 '{"epoch": 3, "seco')          # torn final write
    records = MetricsLogger.read_records(str(p))
    assert [r["epoch"] for r in records] == [1, 2]


def test_read_records_raises_on_mid_file_corruption(tmp_path):
    p = tmp_path / "metrics.jsonl"
    p.write_text('{"epoch": 1}\nGARBAGE\n{"epoch": 2}\n')
    with pytest.raises(ValueError, match="malformed"):
        MetricsLogger.read_records(str(p))


def test_obs_report_summarizes_a_run(tmp_path, capsys):
    trainer = Trainer(_cfg(checkpoint=CheckpointConfig(
        directory=str(tmp_path), save_best=False, save_last=False)))
    try:
        trainer.train()
    finally:
        trainer.close()
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "scripts"))
    try:
        import obs_report
    finally:
        sys.path.pop(0)
    assert obs_report.main([str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "== epochs ==" in out
    assert "step time / stalls" in out
    assert "input-stall" in out


def test_nan_guard_raises_and_preserves_no_checkpoint(tmp_path):
    cfg = _cfg(checkpoint=CheckpointConfig(
        directory=str(tmp_path / "ck"), save_best=False, save_last=True))
    trainer = Trainer(cfg)
    try:
        trainer.state = trainer.state.replace(
            params=jax.tree_util.tree_map(
                lambda p: p * jnp.nan, trainer.state.params))
        with pytest.raises(FloatingPointError, match="non-finite"):
            trainer.train()
        # the guard fired BEFORE save_state: no poisoned resume point
        assert trainer.ckpt.latest_step() is None
    finally:
        trainer.close()
