"""Per-op HBM byte attribution + bytes-budget gate + phase attribution
(tpunet/obs/hlo_bytes.py, tpunet/obs/device_time.py,
scripts/check_bytes_budget.py)."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpunet.obs import hlo_bytes
from tpunet.obs.device_time import phase_times

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "scripts"))

from check_bytes_budget import check_record  # noqa: E402


# ---------------------------------------------------------------- parser

def test_parsed_total_tracks_cost_analysis():
    """The text-parsed byte total must track XLA's own cost analysis
    on a real compiled module (same accounting model)."""

    @jax.jit
    def f(x, w):
        with jax.named_scope("tpunet_fwd_bwd"):
            y = jax.nn.relu(x @ w)
        with jax.named_scope("tpunet_optimizer"):
            return y * 2.0 + 1.0, jnp.sum(y)

    x = jnp.ones((256, 128))
    w = jnp.ones((128, 64))
    compiled = f.lower(x, w).compile()
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    want = float(ca.get("bytes accessed", 0.0))
    got = hlo_bytes.breakdown(compiled.as_text())["total"]
    assert want > 0 and abs(got - want) / want < 0.05


def test_breakdown_categories_and_gauges():
    @jax.jit
    def f(x, w):
        return jnp.sum(x @ w)

    compiled = f.lower(jnp.ones((64, 32)), jnp.ones((32, 16))).compile()
    per_image = hlo_bytes.per_image_breakdown(compiled.as_text(), 64)
    assert per_image["total"] > 0
    assert set(per_image) - {"total"} <= set(hlo_bytes.CATEGORIES)

    from tpunet.obs.registry import Registry
    reg = Registry()
    hlo_bytes.emit_gauges(reg, per_image)
    snap = reg.snapshot()
    assert snap["hbm_bytes_per_image_total"] == float(per_image["total"])


def test_shape_bytes():
    assert hlo_bytes._shape_bytes("f32[8,16,16,32]{3,2,1,0}") \
        == 8 * 16 * 16 * 32 * 4
    assert hlo_bytes._shape_bytes("bf16[4,4]") == 32
    assert hlo_bytes._shape_bytes("f32[]") == 4
    assert hlo_bytes._shape_bytes("(f32[2], u8[3])") == 11
    assert hlo_bytes._shape_bytes("token[]") == 0


def test_categorize_markers():
    fwd = ("jit(train_step)/jit(main)/tpunet_fwd_bwd/jvp(MobileNetV2)/"
           "stem/conv/conv_general_dilated")
    bwd = ("jit(train_step)/jit(main)/tpunet_fwd_bwd/"
           "transpose(tpunet_fwd_bwd)/jvp(MobileNetV2)/stem/conv/"
           "conv_general_dilated")
    bn = ("jit(train_step)/jit(main)/tpunet_fwd_bwd/jvp(MobileNetV2)/"
          "stem/bn/reduce_sum")
    opt = "jit(train_step)/jit(main)/tpunet_optimizer/add"
    assert hlo_bytes.categorize("convolution", fwd) == "conv_fwd"
    assert hlo_bytes.categorize("convolution", bwd) == "conv_bwd"
    assert hlo_bytes.categorize("fusion", bn) == "bn"
    assert hlo_bytes.categorize("fusion", opt) == "optimizer"
    assert hlo_bytes.categorize("copy", "") == "copy_pad"
    assert hlo_bytes.categorize("all-reduce", "x") == "collective"
    assert hlo_bytes.phase_of(fwd) == "fwd"
    assert hlo_bytes.phase_of(bwd) == "bwd"
    assert hlo_bytes.phase_of(opt) == "optimizer"


# ----------------------------------------------------- phase attribution

def test_phase_times_from_hlo_stats_rows():
    rows = [
        {"Framework op name": "jit(s)/tpunet_fwd_bwd/jvp(M)/x",
         "Total self time (us)": "30"},
        {"Framework op name":
         "jit(s)/tpunet_fwd_bwd/transpose(tpunet_fwd_bwd)/jvp(M)/x",
         "Total self time (us)": "50"},
        {"Framework op name": "jit(s)/tpunet_optimizer/add",
         "Total self time (us)": "15"},
        {"Framework op name": "jit(s)/tpunet_ema/mul",
         "Total self time (us)": "5"},
        {"Framework op name": None, "Total self time (us)": "bad"},
    ]
    out = phase_times(rows)
    assert out["fwd"]["us"] == 30 and out["bwd"]["us"] == 50
    assert out["optimizer"]["us"] == 15 and out["ema"]["us"] == 5
    assert abs(sum(r["pct"] for r in out.values()) - 100.0) < 0.1
    assert list(out)[0] == "bwd"  # ordered by time


def test_obs_report_trace_degrades_without_a_trace(tmp_path):
    """--trace on a directory with no xplane under it must degrade to
    a note, not a crash (with a trace and the programs' texts it needs
    JAX alone: tests/benchmark/test_benchmark_scopes.py)."""
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    import obs_report
    phases, notes = obs_report.device_phases(str(tmp_path))
    assert phases is None and any("unavailable" in n for n in notes)


# ----------------------------------------------------------- budget gate

def _record(measured, kind="TPU v5 lite", breakdown=None):
    return {"device_kind": kind,
            "xla_bytes_accessed_per_image": measured,
            "bytes_per_image_breakdown": breakdown}


def _budget(budgeted, tol=5, breakdown=None):
    entry = {"xla_bytes_accessed_per_image": budgeted}
    if breakdown:
        entry["breakdown"] = breakdown
    return {"tolerance_pct": tol, "budgets": {"TPU v5 lite": entry}}


def test_budget_within_tolerance_passes():
    ok, msgs = check_record(_record(103e6), _budget(100e6))
    assert ok and any("OK" in m for m in msgs)


def test_budget_regression_fails():
    ok, msgs = check_record(_record(106e6), _budget(100e6))
    assert not ok and any("REGRESSION" in m for m in msgs)


def test_budget_unknown_device_passes_with_note():
    ok, msgs = check_record(_record(999e6, kind="cpu"), _budget(100e6))
    assert ok and any("no bytes budget" in m for m in msgs)


def test_budget_missing_measurement_skips():
    ok, msgs = check_record(_record(None), _budget(100e6))
    assert ok and any("no measurement" in m for m in msgs)


def test_budget_breakdown_category_gate():
    rec = _record(100e6, breakdown={"conv_bwd": 50e6})
    ok, _ = check_record(rec, _budget(100e6, breakdown={"conv_bwd": 45e6}))
    assert not ok
    ok, _ = check_record(rec, _budget(100e6, breakdown={"conv_bwd": 49e6}))
    assert ok


def _load_checked_in_budget():
    with open(os.path.join(REPO, "docs", "bytes_budget.json")) as fp:
        return json.load(fp)


BENCH_FIXTURES = os.path.join(REPO, "tests", "fixtures", "bench")
BENCH_R05 = os.path.join(BENCH_FIXTURES, "BENCH_r05.json")


def _bench_artifacts():
    """[(round, parsed record)] for every BENCH_r*.json record kept
    under tests/fixtures/bench/ (pretty-printed, the bench.py record
    under 'parsed' — the shape the driver stores), oldest first.
    BENCH_rN measures the tree AFTER PR N-1."""
    import glob
    import re
    out = []
    for path in glob.glob(os.path.join(BENCH_FIXTURES, "BENCH_r*.json")):
        m = re.search(r"BENCH_r(\d+)\.json$", path)
        if not m:
            continue
        with open(path) as fp:
            data = json.load(fp)
        rec = data.get("parsed") if isinstance(data, dict) else None
        if isinstance(rec, dict):
            out.append((int(m.group(1)), rec))
    return sorted(out, key=lambda t: t[0])


def test_checked_in_budget_file_is_valid():
    """Structural validity: positive totals, real category names, and
    category budgets that sum to no more than the total allows."""
    budget = _load_checked_in_budget()
    tol = budget["tolerance_pct"] / 100.0
    assert tol > 0
    for kind, entry in budget["budgets"].items():
        total = entry["xla_bytes_accessed_per_image"]
        assert total > 0, kind
        bd = {k: v for k, v in (entry.get("breakdown") or {}).items()
              if not k.startswith("_")}
        assert set(bd) <= set(hlo_bytes.CATEGORIES), (kind, set(bd))
        assert all(v > 0 for v in bd.values()), (kind, bd)
        assert sum(bd.values()) <= total * (1 + tol), \
            (kind, sum(bd.values()), total)


def test_budget_vs_latest_bench_artifact():
    """Budget/measurement drift fails tier-1 instead of waiting for a
    slow bench run: every BENCH_r* record measuring this-or-newer
    trees (round > the entry's as_of_round; BENCH_rN measures the
    tree after PR N-1) must PASS the checked-in budget, and the budget
    must not sit above the latest matching measurement (a stale or
    wishful budget would mask regressions)."""
    budget = _load_checked_in_budget()
    tol = budget["tolerance_pct"] / 100.0
    arts = _bench_artifacts()
    assert arts, "no BENCH_r*.json artifacts found"
    for kind, entry in budget["budgets"].items():
        matching = [(rnd, rec) for rnd, rec in arts
                    if kind.lower() in (rec.get("device_kind") or "").lower()]
        if not matching:
            continue
        # Drift gate: artifacts measuring the budgeted tree (or newer).
        for rnd, rec in matching:
            if rnd > entry.get("as_of_round", 0):
                ok, msgs = check_record(rec, budget)
                assert ok, (f"BENCH_r{rnd:02d} fails the checked-in "
                            f"budget — ratchet/reconcile "
                            f"docs/bytes_budget.json", msgs)
        # Staleness gate: the budget may anticipate a measured lever
        # (ratchet + as_of_round bump) but never EXCEED the last
        # measured reality by more than tolerance.
        latest_total = matching[-1][1].get("xla_bytes_accessed_per_image")
        if latest_total:
            assert entry["xla_bytes_accessed_per_image"] <= \
                latest_total * (1 + tol), \
                (kind, entry["xla_bytes_accessed_per_image"], latest_total)


# ------------------------------------------------------------- end-to-end

@pytest.mark.slow
def test_bench_smoke_emits_breakdown_and_enforces_budget(tmp_path):
    """bench.py --smoke --enforce-budget: the JSON carries the
    bytes_per_image_breakdown field tracking xla_bytes_accessed, and
    the gate exits 0 on CPU (no CPU budget to enforce)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--smoke",
         "--enforce-budget"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=800)
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads([ln for ln in out.stdout.splitlines()
                      if ln.startswith("{")][-1])
    bd = rec["bytes_per_image_breakdown"]
    assert bd and bd["total"] > 0
    assert abs(bd["total"] - rec["xla_bytes_accessed_per_image"]) \
        / rec["xla_bytes_accessed_per_image"] < 0.05
    assert "nothing to enforce" in out.stderr


def test_async_collectives_counted_once_as_collective():
    assert hlo_bytes.categorize("all-reduce-start", "") == "collective"
    assert hlo_bytes.categorize("collective-permute-start", "") \
        == "collective"
    text = """HloModule m

ENTRY %main.1 (p0: f32[256]) -> f32[256] {
  %p0 = f32[256]{0} parameter(0)
  %ars = f32[256]{0} all-reduce-start(f32[256]{0} %p0), to_apply=%add
  %ard = f32[256]{0} all-reduce-done(f32[256]{0} %ars)
  ROOT %mul = f32[256]{0} multiply(f32[256]{0} %ard, f32[256]{0} %ard)
}
"""
    rows = list(hlo_bytes.instruction_bytes(text))
    cats = {cat for _op, cat, _b, _n in rows}
    assert "collective" in cats
    coll = sum(b for _op, cat, b, _n in rows if cat == "collective")
    assert coll == 2 * 256 * 4  # the -start's operand+output, ONCE


def test_budget_cli_accepts_pretty_printed_artifact(tmp_path, capsys):
    """The documented `check_bytes_budget.py BENCH_r05.json` invocation
    must parse the pretty-printed driver artifact, not crash. (Checked
    against r05's own value, not the checked-in budget — the ratcheted
    budget describes a NEWER tree than the r05 artifact measures.)"""
    from check_bytes_budget import main as budget_main
    b = tmp_path / "budget.json"
    b.write_text(json.dumps(_budget(139e6)))
    rc = budget_main([BENCH_R05, "--budget", str(b)])
    out = capsys.readouterr().out
    assert rc == 0 and "xla_bytes_accessed_per_image" in out


def test_budget_cli_flag_order_and_missing_value(tmp_path, capsys):
    """--budget may precede or follow the record path (mirroring
    check_serve_budget); a trailing --budget with no value or a
    missing record path is a usage error, not a crash."""
    from check_bytes_budget import main as budget_main
    b = tmp_path / "budget.json"
    b.write_text(json.dumps(_budget(139e6)))
    art = BENCH_R05
    assert budget_main(["--budget", str(b), art]) == 0
    assert budget_main([art, "--budget", str(b)]) == 0
    assert budget_main([art, "--budget"]) == 2
    assert budget_main(["--budget", str(b)]) == 2  # no record path


def test_budget_breakdown_annotation_keys_and_missing_breakdown():
    """'_'-prefixed breakdown keys are annotations (never gated), and
    a record with no breakdown at all passes budgeted categories with
    a note — the r05-style artifact predates the field."""
    bud = _budget(100e6, breakdown={"_source": "estimate",
                                    "conv_bwd": 45e6})
    ok, msgs = check_record(_record(100e6, breakdown=None), bud)
    assert ok and any("no bytes_per_image_breakdown" in m for m in msgs)
    assert not any("_source" in m for m in msgs)
    ok, _ = check_record(_record(100e6, breakdown={"conv_bwd": 44e6}), bud)
    assert ok
    ok, _ = check_record(_record(100e6, breakdown={"conv_bwd": 50e6}), bud)
    assert not ok


def test_augment_scope_gets_its_own_bucket():
    aug = ("jit(train_step)/jit(main)/tpunet_fwd_bwd/tpunet_augment/"
           "dot_general")
    assert hlo_bytes.categorize("dot", aug) == "augment"
    assert hlo_bytes.phase_of(aug) == "augment"
    # ...and it shows up end to end in a real train-step lowering.
    from tpunet.config import (CheckpointConfig, DataConfig, MeshConfig,
                               ModelConfig, OptimConfig, TrainConfig)
    from tpunet.data.cifar10 import synthetic_cifar10
    from tpunet.parallel import shard_host_batch
    from tpunet.train.loop import Trainer
    from tpunet.utils.prng import step_key
    batch = 8
    cfg = TrainConfig(
        data=DataConfig(dataset="synthetic", batch_size=batch,
                        image_size=32),
        model=ModelConfig(width_mult=0.5, dtype="float32"),
        optim=OptimConfig(), mesh=MeshConfig(),
        checkpoint=CheckpointConfig(save_best=False, save_last=False))
    t = Trainer(cfg, dataset=synthetic_cifar10(n_train=2 * batch,
                                               n_test=batch))
    try:
        rng = np.random.default_rng(0)
        x = rng.integers(0, 256, size=(batch, 32, 32, 3), dtype=np.uint8)
        y = rng.integers(0, 10, size=batch).astype(np.int32)
        gx, gy = shard_host_batch(t.mesh, x, y)
        compiled = t.train_step.lower(t.state, gx, gy,
                                      step_key(0, 0)).compile()
        bd = hlo_bytes.breakdown(compiled.as_text())
        assert bd.get("augment", 0) > 0
    finally:
        t.close()
