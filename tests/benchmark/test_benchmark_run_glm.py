"""The glm-4.7-flash train cell: ``run.py``'s stages at a tiny size
(after ``test_benchmark_run_lm.py`` and ``test_benchmark_run_dots3.py``)
— the reference agrees with the program's float32 path through
``Trainer.train_one_epoch``, the float8 control does not, a step that
returns its state unchanged is not correct — and the cell, its
configuration, its metrics and their readers as the issue names them."""

import json

import pytest

import bench_tiny_glm
import bench_tiny_root
from benchmark import harness, opcount_latent, run

CELL = bench_tiny_glm.CELL
REPO = bench_tiny_root.REPO
LIMITS = {"loss_gap_step1": 1e-4, "loss_gap_step2": 1e-4,
          "loss_gap_step3": 1e-4, "grad_norm_gap": 2e-3,
          "delta_norm_gap": 0.3, "grad_norm_gap_global": 1e-3,
          "delta_norm_gap_global": 0.1, "rows_not_in_dataset": 0,
          "nonfinite_window_losses": 0}
SCOPED = ("mla_fwd_bwd_ms.train", "moe_fwd_bwd_ms.train",
          "mtp_fwd_bwd_ms.train", "head_loss_ms.train",
          "latent_train_scope_unattributed_pct.train")
COUNTED = ("moe_held_pair_share_pct.train", "moe_load_max_over_mean.train",
           "step_mfu_pct.train")
SHARED = ("device_idle_pct.train", "kernel_time_pct.train", "step_ms.train",
          "data_wait_pct.train", "peak_hbm_pct.train",
          "compiles_in_window.train", "fwd_bwd_ms.train",
          "optimizer_ms.train", "bwd_share_pct.train",
          "scope_unattributed_pct.train", "idle_unattributed_pct.train")


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
def root(tmp_path_factory):
    root = bench_tiny_glm.make(str(tmp_path_factory.mktemp("bench")))
    bench_tiny_root.set_limits(root, CELL, LIMITS)
    return root


@pytest.fixture(scope="module")
def sound(root, tmp_path_factory):
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
    # every leaf of the tree was compared, the module's and the fixed
    # bias among them
    leaves = set(ref["leaf_gaps"]["grad_norms"])
    assert {"mtp/eh_proj", "mtp/block/moe/experts_down",
            "block01/moe/router_bias", "block00/mlp_gate", "head"} <= leaves
    assert ref["leaf_gaps"]["grad_norms"]["block01/moe/router_bias"][0] == 0


def test_float8_control_comes_out_not_correct(sound):
    _, _, ref = sound
    low = ref["control"]
    assert low["grad_norm_gap"] > LIMITS["grad_norm_gap"]
    assert low["grad_norm_gap"] > 100 * ref["numbers"]["grad_norm_gap"]


def test_traced_rehearsal_names_the_counted_metrics_and_times_nothing(sound):
    ctx, prog, ref = sound
    # no device trace off the TPU: the scoped metrics are left out, the
    # ones the step counts are there (their values withheld: a rehearsal
    # writes no number under a device metric's name)
    assert set(COUNTED) <= set(prog["metrics"])
    assert not set(SCOPED) & set(prog["metrics"])
    assert all(v is None for v in prog["metrics"].values())
    line = run.final_line(ctx["cell"], False, prog, ref)
    assert line["metrics"] == {} and line["device"]["platform"] == "cpu"
    json.dumps(line)


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
    assert ref["numbers"]["delta_norm_gap_global"] == pytest.approx(
        1.0, abs=1e-4)


# -- the cell as the issue names it -------------------------------------------

def test_the_cell_is_the_one_the_issue_names():
    cell = harness.load_cell(CELL, REPO)
    config, traffic = cell["config"], cell["traffic"]
    assert cell["entry"]["chips"] == 1 and len(cell["entry"]["why"]) <= 200
    assert cell["cell"]["runner"] == "train"
    assert cell["cell"]["section"] == "train"
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (5, 8, 19360)
    assert (config["num_hidden_layers_published"],
            config["n_routed_experts_published"],
            config["vocab_size_published"]) == (47, 64, 154880)
    assert config["held_experts"] == list(range(8))
    assert (traffic["kind"], traffic["data"], traffic["batch"],
            traffic["seq_len"], traffic["steps_per_chunk"]) == (
        "train", "lm_tokens", 1, 8192, 8)
    for key in ("deployment", "changed", "assumed"):
        assert config[key], key
    assert "memory_analysis" in config["train"]
    model = config["program"]["model"]
    assert model["name"] == "latent_lm" and model["remat"] is True
    assert model["param_dtype"] == "float32" == config["param_dtype"]
    assert model["mtp_loss_weight"] == config["mtp_loss_weight"] == 0.3
    assert model["vocab_size"] == config["program"]["data"]["vocab_size"] \
        == config["vocab_size"]
    assert model["max_seq_len"] == config["program"]["data"]["seq_len"] \
        == traffic["seq_len"]
    assert config["program"]["optim"] == config["optimizer"]
    # the program's sizes are the configuration's, key for key
    for key, value in model["latent"].items():
        if key == "layer_types":
            assert value == ["full_attention"] * 5
        elif key == "n_routed_experts":
            assert value == config["n_routed_experts_published"] == 64
        elif key == "attention_gate_type":
            assert value is None and key not in config
        else:
            assert value == config[key], key
    for absent in ("index_topk", "apply_mla_qkv_lora_rescale"):
        assert absent not in model["latent"] and absent not in config
    reported = {m["name"] for m in cell["per_layer"]}
    assert reported == set(SCOPED) | set(COUNTED) | set(SHARED)
    assert {m["name"] for m in cell["end_to_end"]} == {"train_items_per_s",
                                                       "setup_s"}
    for m in cell["per_layer"]:
        assert m["moves"] == "train_items_per_s"


def test_the_published_keys_are_the_catalogs():
    """Every key of the configuration that is not in ``reduced`` reads
    as the public config does — kept as data here: the widths this PR
    may never cut."""
    config = harness.load_cell(CELL, REPO)["config"]
    published = {
        "attention_bias": False, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 10240, "max_position_embeddings": 202752,
        "model_type": "glm4_moe_lite", "moe_intermediate_size": 1536,
        "topk_method": "noaux_tc", "norm_topk_prob": True,
        "num_attention_heads": 20, "n_group": 1, "topk_group": 1,
        "n_shared_experts": 1, "routed_scaling_factor": 1.8,
        "num_experts_per_tok": 4, "first_k_dense_replace": 1,
        "num_key_value_heads": 20, "num_nextn_predict_layers": 1,
        "partial_rotary_factor": 1, "rms_norm_eps": 1e-05,
        "rope_scaling": None, "rope_theta": 1000000,
        "tie_word_embeddings": False, "q_lora_rank": 768,
        "kv_lora_rank": 512, "qk_nope_head_dim": 192,
        "qk_rope_head_dim": 64, "v_head_dim": 256}
    for key, value in published.items():
        assert config[key] == value, key


def test_opcount_latent_against_a_hand_count_at_the_published_widths():
    config = harness.load_cell(CELL, REPO)["config"]
    n = opcount_latent.layer_counts(config)
    attention = (2048 * 768 + 768 + 768 * 20 * 256 + 2048 * 576 + 512
                 + 512 * 20 * 448 + 20 * 256 * 2048)
    assert n["attention"] == attention == 21_759_232
    assert n["dense_layer"] == 84_677_888
    assert n["expert"] == 3 * 2048 * 1536 == 9_437_184
    assert n["expert_layer_outside_routed"] == 31_331_648
    assert n["expert_layer"] == 106_829_120
    assert n["embedding"] == n["head"] == 39_649_280
    assert 2 * 2048 + n["w_eh"] + n["expert_layer"] + 2048 == 115_223_872
    assert opcount_latent.parameters(config) == 706_518_848
    ref = harness.load_reference(harness.load_cell(CELL, REPO))
    assert sum(int(__import__("math").prod(shape)) for shape, _, _ in
               ref.param_spec(config, "train").values()) == 706_518_848
    active = opcount_latent.activated_parameters(config, 0.125)
    assert active == 352_583_680            # the issue's 352.6 M
    # 6 x activated x tokens + causal scores and values over six
    # attentions, forward + backward: 17.3 + 12.4 TFLOP a step
    step = 8192 * opcount_latent.train_flops_per_token(config, 8192, 0.125)
    assert 6 * active * 8192 == pytest.approx(17.33e12, rel=1e-3)
    assert step - 6 * active * 8192 == pytest.approx(12.37e12, rel=1e-3)
    # every pair held: all four chosen experts count
    assert opcount_latent.activated_parameters(config, 1.0) - active == \
        pytest.approx(5 * 4 * 0.875 * 9_437_184)


def test_step_mfu_reader_counts_from_the_runs_own_share(monkeypatch):
    from tpunet.train import metrics as M

    cell = harness.load_cell(CELL, REPO)
    read = harness.load_reader(cell, "step_mfu_latent").read
    obs = {"host": {"items_per_s": 16000.0, "seq_len": 8192, "batch": 1},
           "cell": cell, "device_kind": "TPU v5 lite"}
    monkeypatch.setattr(M, "STEP_MEAN_TOTALS", {})
    assert read(obs, {}) is None            # a program without the counter
    monkeypatch.setattr(M, "STEP_MEAN_TOTALS",
                        {"steps": 8.0, "moe_held_pair_share": 1.0})
    want = 100.0 * opcount_latent.train_flops_per_token(
        cell["config"], 8192, 0.125) * 16000.0 / 197e12
    assert read(obs, {}) == pytest.approx(want) and 25 < want < 35
    assert read({**obs, "host": {"seq_len": 8192}}, {}) is None


@pytest.mark.parametrize("metric", SCOPED)
def test_the_scoped_metrics_read_nothing_without_a_trace(metric):
    cell = harness.load_cell(CELL, REPO)
    m = next(x for x in cell["per_layer"] if x["name"] == metric)
    assert m["reader"] == "scope_list" and json.dumps(m["params"]["scopes"])
    read = harness.load_reader(cell, "scope_list").read
    assert read({"host": {}, "trace": None}, m["params"]) is None


def test_the_train_scope_list_names_the_steps_operations():
    from tpunet.obs import device_time

    spec = harness.load_json("benchmark", "layer_metrics",
                             "moe_fwd_bwd_ms.train.json", root=REPO)
    scope = device_time.classifier([tuple(p) for p in
                                    spec["params"]["scopes"]])
    fwd = "jit(train_step)/tpunet_fwd_bwd/jvp(LatentLM)/"
    bwd = ("jit(train_step)/tpunet_fwd_bwd/transpose(jvp(LatentLM))/"
           "tpunet_fwd_bwd/jvp(LatentLM)/checkpoint/")
    again = bwd + "rematted_computation/"
    for at in (fwd, bwd, again):
        assert scope(at + "block01/attn/tpunet_mla_full/dot_general") == "mla"
        assert scope(at + "block01/attn/tpunet_mla_full/tpunet_flash_fwd/"
                     "pallas_call") == "mla"
        assert scope(at + "block02/moe/tpunet_moe_router/top_k") == \
            "moe_router"
        assert scope(at + "block02/moe/tpunet_moe_experts/sort") == \
            "moe_experts"
        assert scope(at + "block02/moe/tpunet_moe_shared/mul") == "moe_shared"
        assert scope(at + "block00/tpunet_dense_mlp/dot_general") == \
            "dense_mlp"
        assert scope(at + "block03/ln2/rsqrt") == "block_other"
    mtp = fwd + "tpunet_mtp/mtp/"
    assert scope(mtp + "block/attn/tpunet_mla_full/dot_general") == "mtp"
    assert scope(mtp + "block/moe/tpunet_moe_experts/sort") == "mtp"
    assert scope(mtp + "dot_general") == "mtp"
    assert scope("ragged-dot-none.4") == "moe_experts"  # the compiler's name
    assert scope(fwd + "tpunet_head/dot_general") == "head_loss"
    assert scope("jit(train_step)/tpunet_fwd_bwd/jvp()/reduce_max") == \
        "head_loss"
    assert scope("jit(train_step)/tpunet_fwd_bwd/transpose(jvp(jit("
                 "take_along_axis)))/scatter-add") == "head_loss"
    assert scope(fwd + "embed/jit(_take)/gather") == "embed"
    assert scope("jit(train_step)/tpunet_optimizer/mul") == "optimizer"
    assert scope("something_else") is None
