"""The smallthinker-21ba3b train cell: ``run.py``'s stages at a tiny
size (after ``test_benchmark_run_glm.py``) — the reference agrees with
the program's float32 path through ``Trainer.train_one_epoch``, the
float8 control does not — and the cell, its configuration, its metrics,
their readers and the counting functions as the issue names them."""

import json
import math

import pytest

import bench_tiny_root
import bench_tiny_smallthinker
from benchmark import harness, opcount_gqa_train, run

CELL = bench_tiny_smallthinker.CELL
REPO = bench_tiny_root.REPO
LIMITS = {"loss_gap_step1": 1e-4, "loss_gap_step2": 1e-4,
          "loss_gap_step3": 1e-4, "grad_norm_gap": 2e-3,
          "delta_norm_gap": 0.3, "grad_norm_gap_global": 1e-3,
          "delta_norm_gap_global": 0.1, "rows_not_in_dataset": 0,
          "nonfinite_window_losses": 0}
SCOPED = ("gqa_window_fwd_bwd_ms.train", "gqa_global_fwd_bwd_ms.train",
          "gqa_train_scope_unattributed_pct.train", "moe_fwd_bwd_ms.train",
          "head_loss_ms.train")
COUNTED = ("moe_held_pair_share_pct.train", "moe_load_max_over_mean.train",
           "step_mfu_gqa_pct.train")
TRACED = ("flash_train_window_roofline_pct.train",)
SHARED = ("device_idle_pct.train", "kernel_time_pct.train", "step_ms.train",
          "data_wait_pct.train", "peak_hbm_pct.train",
          "compiles_in_window.train", "fwd_bwd_ms.train",
          "optimizer_ms.train", "bwd_share_pct.train",
          "scope_unattributed_pct.train", "idle_unattributed_pct.train",
          "idle_epoch_edge_pct.train", "idle_host_other_pct.train")


@pytest.fixture(scope="module", autouse=True)
def one_device():
    """The cell's one chip: the runner builds the trainer's mesh over
    every device JAX has (eight virtual ones under the tests), and
    ``latent_lm`` has no mesh lowering."""
    import jax

    from tpunet.train import loop

    real, patch = loop.make_mesh, pytest.MonkeyPatch()
    patch.setattr(loop, "make_mesh",
                  lambda cfg=None: real(cfg, jax.devices()[:1]))
    yield
    patch.undo()


@pytest.fixture(scope="module")
def sound(tmp_path_factory):
    root = bench_tiny_smallthinker.make(
        str(tmp_path_factory.mktemp("bench")))
    bench_tiny_root.set_limits(root, CELL, LIMITS)
    ctx = bench_tiny_root.context(
        root, CELL, str(tmp_path_factory.mktemp("work")), control="fp8",
        trace=1)
    prog = run.run_stage(ctx, "program")
    ref = run.run_stage(ctx, "reference")
    return ctx, prog, ref


def test_reference_agrees_with_the_float32_program(sound):
    _, prog, ref = sound
    assert ref["correct"] is True
    for name, limit in LIMITS.items():
        assert ref["numbers"][name] <= limit, name
    assert prog["attempted"] >= 4 and prog["failed"] == 0
    # every leaf of the tree was compared: the block's own router, the
    # grouped projections, the held experts; no shared expert to compare
    leaves = set(ref["leaf_gaps"]["grad_norms"])
    assert {"block00/router", "block03/router", "block01/attn/k_proj",
            "block02/moe/experts_down", "head"} <= leaves
    assert not [p for p in leaves if "shared" in p or "/moe/router" in p]
    assert ref["leaf_gaps"]["grad_norms"]["block00/router"][0] > 0


def test_float8_control_comes_out_not_correct(sound):
    _, _, ref = sound
    low = ref["control"]
    assert low["grad_norm_gap"] > LIMITS["grad_norm_gap"]
    assert low["grad_norm_gap"] > 100 * ref["numbers"]["grad_norm_gap"]


def test_traced_rehearsal_names_the_counted_metrics_and_times_nothing(sound):
    ctx, prog, ref = sound
    # no device trace off the TPU: the scoped metrics and the kernels'
    # roofline share are left out, the ones the step counts are there
    # (their values withheld: a rehearsal writes no number under a
    # device metric's name)
    assert set(COUNTED) <= set(prog["metrics"])
    assert not set(SCOPED + TRACED) & set(prog["metrics"])
    assert all(v is None for v in prog["metrics"].values())
    line = run.final_line(ctx["cell"], False, prog, ref)
    assert line["metrics"] == {} and line["device"]["platform"] == "cpu"
    json.dumps(line)


# -- the cell as the issue names it -------------------------------------------

def test_the_cell_is_the_one_the_issue_names():
    cell = harness.load_cell(CELL, REPO)
    config, traffic = cell["config"], cell["traffic"]
    assert cell["entry"]["chips"] == 1 and len(cell["entry"]["why"]) <= 200
    assert cell["entry"]["why"] == cell["cell"]["why"]
    assert cell["cell"]["runner"] == "train"
    assert cell["cell"]["section"] == "train"
    assert set(cell["cell"]["limits"]) == set(LIMITS)
    assert set(cell["cell"]["limits_set_from"]) >= {
        "seeds", "grad_norm_gap", "grad_norm_gap_global", "delta_norm_gap",
        "delta_norm_gap_global", "loss_gap_step*"}
    assert config["reduced"] == ["num_hidden_layers",
                                 "moe_num_primary_experts", "vocab_size"]
    assert set(config["changed"]) == set(config["reduced"])
    assert (config["num_hidden_layers"], config["moe_num_primary_experts"],
            config["vocab_size"]) == (4, 16, 37984)
    assert (config["num_hidden_layers_published"],
            config["moe_num_primary_experts_published"],
            config["vocab_size_published"]) == (52, 64, 151936)
    assert 4 * config["vocab_size"] == config["vocab_size_published"]
    assert config["held_experts"] == list(range(16))
    assert (traffic["kind"], traffic["data"], traffic["batch"],
            traffic["seq_len"], traffic["steps_per_chunk"]) == (
        "train", "lm_tokens", 1, 8192, 8)
    for key in ("deployment", "changed", "assumed", "precision_stated"):
        assert config[key], key
    assert "memory_analysis" in config["train"]
    model = config["program"]["model"]
    assert model["name"] == "latent_lm" and model["remat"] is True
    assert model["param_dtype"] == "float32" == config["param_dtype"]
    assert model["vocab_size"] == config["program"]["data"]["vocab_size"] \
        == config["vocab_size"]
    assert model["max_seq_len"] == config["program"]["data"]["seq_len"] \
        == traffic["seq_len"]
    assert config["program"]["optim"] == config["optimizer"]
    # the program's sizes are the configuration's, key for key
    for key, value in model["latent"].items():
        if key in ("rope_layout", "sliding_window_layout"):
            assert value == config[key][:4] == [0, 1, 1, 1]
        elif key == "moe_num_primary_experts":
            assert value == config["moe_num_primary_experts_published"] == 64
        else:
            assert value == config[key], key
    reported = {m["name"] for m in cell["per_layer"]}
    assert reported == set(SCOPED) | set(COUNTED) | set(TRACED) | set(SHARED)
    assert {m["name"] for m in cell["end_to_end"]} == {"train_items_per_s",
                                                       "setup_s"}
    for m in cell["per_layer"]:
        assert m["moves"] == "train_items_per_s"
        assert harness.load_reader(cell, m["reader"]).read


def test_the_published_keys_are_the_catalogs():
    """Every key of the configuration that is not in ``reduced`` reads
    as the public config does — kept as data here: the widths this PR
    may never cut."""
    config = harness.load_cell(CELL, REPO)["config"]
    layout = [0, 1, 1, 1] * 13
    published = {
        "head_dim": 128, "hidden_size": 2560,
        "max_position_embeddings": 16384,
        "model_name": "smallthinker_21b_instruct",
        "moe_ffn_hidden_size": 768, "moe_num_active_primary_experts": 6,
        "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
        "num_attention_heads": 28, "num_key_value_heads": 4,
        "rms_norm_eps": 1e-06, "rope_layout": layout, "rope_scaling": None,
        "rope_theta": 1500000, "sliding_window_layout": layout,
        "sliding_window_size": 4096, "tie_word_embeddings": False}
    for key, value in published.items():
        assert config[key] == value, key


def test_opcount_gqa_train_against_a_hand_count_at_the_published_widths():
    cell = harness.load_cell(CELL, REPO)
    config = cell["config"]
    n = opcount_gqa_train.layer_counts(config)
    assert n["attention"] == 2560 * 3584 * 2 + 2 * 2560 * 512 == 20_971_520
    assert n["router"] == 163_840 and n["expert"] == 5_898_240
    assert n["layer"] == 20_971_520 + 163_840 + 5_120 + 16 * 5_898_240 \
        == 115_512_320
    assert n["embedding"] == n["head"] == 97_239_040
    assert opcount_gqa_train.parameters(config) == 656_529_920
    ref = harness.load_reference(cell)
    assert sum(math.prod(shape) for shape, _, _ in
               ref.param_spec(config, "train").values()) == 656_529_920
    assert opcount_gqa_train.layer_kinds(config) == (3, 1)
    pairs = opcount_gqa_train.attention_pairs(8192, config)
    assert pairs == {"window": 4096 * 4097 // 2 + 4096 * 4096,
                     "global": 8192 * 8193 // 2}
    # the issue's sizing, a step of 8192 tokens forward: projections
    # 1.37, scores and values 1.56 (0.48 the global layer, 0.36 a
    # windowed one), held experts 0.58, head 1.59, router 0.01 TFLOP
    attn = opcount_gqa_train.attention_flops_forward(8192, config)
    assert 4 * 28 * 128 * pairs["global"] == pytest.approx(0.481e12, rel=2e-3)
    assert 4 * 28 * 128 * pairs["window"] == pytest.approx(0.361e12, rel=2e-3)
    assert attn == 4 * 28 * 128 * (3 * pairs["window"] + pairs["global"])
    active = opcount_gqa_train.activated_parameters(config, 0.25)
    assert active == 4 * (20_971_520 + 163_840 + 6 * 0.25 * 5_898_240) \
        + 97_239_040
    step = 8192 * opcount_gqa_train.train_flops_per_token(config, 8192, 0.25)
    assert step == pytest.approx(3 * (2 * 8192 * active + attn))
    assert step / 3 == pytest.approx(5.12e12, rel=1e-3)
    assert step == pytest.approx(15.4e12, rel=3e-3)
    # the kernels of a step: forward, the forward again (remat), and the
    # backward's five products to the forward's two; 16 launches
    kernels = opcount_gqa_train.flash_train(8192, config)
    assert kernels["kernels"] == 16
    assert kernels["flops"] == pytest.approx(attn * (2 + 2.5))
    # 2.5 GB moved against 7.0 TFLOP: the operations bound the roofline
    peaks = harness.peaks_for("TPU v5 lite", REPO)
    assert opcount_gqa_train.roofline_seconds(kernels, peaks) == \
        kernels["flops"] / 197e12 > 10 * kernels["bytes"] / 819e9
    kept = opcount_gqa_train.flash_train(
        8192, {**config, "program": {"model": {"remat": False}}})
    assert kept["kernels"] == 12
    assert kept["flops"] == pytest.approx(attn * 3.5)
    # a row no longer than the window is all triangle
    assert opcount_gqa_train.attention_pairs(4096, config)["window"] == \
        4096 * 4097 // 2


def test_step_mfu_reader_counts_from_the_runs_own_share(monkeypatch):
    from tpunet.train import metrics as M

    cell = harness.load_cell(CELL, REPO)
    read = harness.load_reader(cell, "step_mfu_gqa").read
    obs = {"host": {"items_per_s": 28000.0, "seq_len": 8192, "batch": 1},
           "cell": cell, "device_kind": "TPU v5 lite"}
    monkeypatch.setattr(M, "STEP_MEAN_TOTALS", {})
    assert read(obs, {}) is None            # a program without the counter
    monkeypatch.setattr(M, "STEP_MEAN_TOTALS",
                        {"steps": 8.0, "moe_held_pair_share": 2.0})
    want = 100.0 * opcount_gqa_train.train_flops_per_token(
        cell["config"], 8192, 0.25) * 28000.0 / 197e12
    assert read(obs, {}) == pytest.approx(want) and 20 < want < 35
    assert read({**obs, "host": {"seq_len": 8192}}, {}) is None


def test_flash_roofline_reader_counts_steps_by_their_kernels():
    from benchmark.trace_reduce import Trace

    cell = harness.load_cell(CELL, REPO)
    spec = next(m for m in cell["per_layer"]
                if m["name"] == "flash_train_window_roofline_pct.train")
    read = harness.load_reader(cell, spec["reader"]).read
    obs = {"host": {"seq_len": 8192, "batch": 1}, "cell": cell,
           "device_kind": "TPU v5 lite", "trace": None}
    assert read(obs, spec["params"]) is None                 # --trace 0
    count = opcount_gqa_train.flash_train(8192, cell["config"])
    least = count["flops"] / 197e12
    # two steps' 32 kernels taking twice the least time: 50 %
    ops = [(f"%tpunet_flash_{'fwd' if i % 2 else 'bwd'}.{i} = custom-call()",
            float(i), 2 * 2 * least / 32) for i in range(32)]
    ops.append(("%fusion.7 = fusion()", 40.0, 1.0))
    obs["trace"] = Trace(device_ops={"/device:TPU:0": ops})
    assert read(obs, spec["params"]) == pytest.approx(50.0)
    obs["trace"] = Trace(device_ops={"/device:TPU:0": ops[-1:]})
    assert read(obs, spec["params"]) is None       # no such operation


@pytest.mark.parametrize("metric", SCOPED)
def test_the_scoped_metrics_read_nothing_without_a_trace(metric):
    cell = harness.load_cell(CELL, REPO)
    m = next(x for x in cell["per_layer"] if x["name"] == metric)
    assert m["reader"] == "scope_list" and json.dumps(m["params"]["scopes"])
    read = harness.load_reader(cell, "scope_list").read
    assert read({"host": {}, "trace": None}, m["params"]) is None


def test_the_new_metrics_share_one_scope_list():
    lists = {json.dumps(harness.load_json(
        "benchmark", "layer_metrics", f"{name}.json",
        root=REPO)["params"]["scopes"]) for name in SCOPED[:3]}
    assert len(lists) == 1


def test_the_train_scope_list_names_the_steps_operations():
    from tpunet.obs import device_time

    spec = harness.load_json("benchmark", "layer_metrics",
                             "gqa_window_fwd_bwd_ms.train.json", root=REPO)
    scope = device_time.classifier([tuple(p) for p in
                                    spec["params"]["scopes"]])
    fwd = "jit(train_step)/tpunet_fwd_bwd/jvp(LatentLM)/"
    bwd = ("jit(train_step)/tpunet_fwd_bwd/transpose(jvp(LatentLM))/"
           "tpunet_fwd_bwd/jvp(LatentLM)/checkpoint/")
    again = bwd + "rematted_computation/"
    for at in (fwd, bwd, again):
        assert scope(at + "block01/attn/tpunet_gqa_window/dot_general") == \
            "gqa_window"
        assert scope(at + "block02/attn/tpunet_gqa_window/tpunet_flash_fwd/"
                     "pallas_call") == "gqa_window"
        assert scope(at + "block00/attn/tpunet_gqa_full/tpunet_flash_bwd/"
                     "pallas_call") == "gqa_global"
        assert scope(at + "block02/tpunet_moe_router/dot_general") == \
            "moe_router"
        assert scope(at + "block02/moe/tpunet_moe_router/top_k") == \
            "moe_router"
        assert scope(at + "block02/moe/tpunet_moe_experts/sort") == \
            "moe_experts"
        assert scope(at + "block03/ln2/rsqrt") == "block_other"
    assert scope("ragged-dot-none.4") == "moe_experts"  # the compiler's name
    assert scope(fwd + "tpunet_head/dot_general") == "head_loss"
    assert scope("jit(train_step)/tpunet_fwd_bwd/jvp()/reduce_max") == \
        "head_loss"
    assert scope(fwd + "embed/jit(_take)/gather") == "embed"
    assert scope("jit(train_step)/tpunet_optimizer/mul") == "optimizer"
    assert scope("something_else") is None
