"""The dots3-note-prev cell at a tiny size: the served tokens are the
float32 reference's best over the vocabulary slice, a token altered
where it is produced comes out as not correct, the float8 control reads
worse than the program, and the cell's own per-layer metrics are left
out without a trace."""

import json
import os

import numpy as np
import pytest

import bench_tiny_dots3
import bench_tiny_root
from benchmark import harness, run, trafficgen

CELL = bench_tiny_dots3.CELL
LIMITS = {"served_logit_gap_max": 1e-3, "tokens_missing": 0,
          "failed_requests": 0}
REPO = bench_tiny_root.REPO
NEW_METRICS = ("decode_latent_attn_ms.serve", "decode_indexer_ms.serve",
               "decode_window_attn_ms.serve", "decode_moe_ms.serve",
               "prefill_indexer_ms.serve",
               "latent_scope_unattributed_pct.serve",
               "decode_device_ms.serve_tput", "prefill_device_ms.serve_tput")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = bench_tiny_dots3.make(str(tmp_path_factory.mktemp("bench")))
    bench_tiny_root.set_limits(root, CELL, LIMITS)
    return root


def test_closed_loop_is_correct_over_the_vocabulary_slice(root, tmp_path,
                                                          capsys):
    ctx = bench_tiny_root.context(root, CELL, str(tmp_path), control="fp8")
    prog = run.run_stage(ctx, "program")
    ref = run.run_stage(ctx, "reference")
    assert ref["correct"] is True and ref["compared_tokens"] >= 6
    assert prog["attempted"] > 0 and prog["failed"] == 0
    assert prog["numbers"]["tokens_missing"] == 0
    # the float8 control lies further from the reference's best than
    # the program does
    assert (ref["control"]["served_logit_gap_max"]
            > 10 * ref["numbers"]["served_logit_gap_max"])
    cap = np.load(os.path.join(str(tmp_path), "capture.npz"))
    assert max(int(cap[k].max()) for k in cap.files) < bench_tiny_dots3.VOCAB
    line = run.final_line(ctx["cell"], False, prog, ref)
    assert line["metrics"] == {}                    # nothing timed off the TPU
    assert set(prog["metrics"]) == {"serve_tok_per_s", "itl_p95_ms",
                                    "setup_s"}


def test_a_token_altered_where_it_is_produced_is_not_correct(
        root, tmp_path, monkeypatch):
    from tpunet.serve.scheduler import GenerateRequest

    real = GenerateRequest.push_token

    def altered(self, token):
        return real(self, (int(token) + 1) % bench_tiny_dots3.VOCAB)

    monkeypatch.setattr(GenerateRequest, "push_token", altered)
    ctx = bench_tiny_root.context(root, CELL, str(tmp_path))
    run.run_stage(ctx, "program")
    ref = run.run_stage(ctx, "reference")
    assert ref["correct"] is False
    assert ref["numbers"]["served_logit_gap_max"] > 1e-3


def test_the_cell_is_the_one_the_issue_names():
    cell = harness.load_cell(CELL, REPO)
    config, traffic = cell["config"], cell["traffic"]
    assert cell["entry"]["chips"] == 1 and len(cell["entry"]["why"]) <= 200
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (5, 32, 19008)
    assert config["held_experts"] == list(range(32))
    lengths = trafficgen.length_pool(traffic)
    assert sorted({p for p, _ in lengths}) == [2054, 2748, 3434, 4096]
    assert sorted({o for _, o in lengths}) == [229, 410, 640, 1145]
    assert min(p for p, _ in lengths) > config["index_topk"]
    serve = cell["cell"]["program"]["serve"]
    assert serve["slots"] == traffic["clients"] == 8
    assert serve["prefill_buckets"] == [4096]
    model = config["program"]["model"]
    assert model["max_seq_len"] >= max(p + o for p, o in lengths)
    reqs = trafficgen.serve_requests(traffic, config, 3000000019, 16)
    assert max(int(r["prompt"].max()) for r in reqs) < config["vocab_size"]
    # the program's sizes are the configuration's, key for key
    for key, value in model["latent"].items():
        if key == "layer_types":
            assert value == config["layer_types"][:5]
        elif key == "n_routed_experts":
            assert value == config["n_routed_experts_published"] == 256
        else:
            assert value == config[key], key
    assert {m["name"] for m in cell["per_layer"]} >= set(NEW_METRICS)


def test_the_published_keys_are_the_catalogs(root):
    """Every key of the configuration that is not in ``reduced`` (or
    added beside the published ones) reads as the public config does —
    kept as data here: the widths this PR may never cut."""
    config = harness.load_cell(CELL, REPO)["config"]
    published = {
        "hidden_size": 5120, "intermediate_size": 13824,
        "moe_intermediate_size": 1536, "num_attention_heads": 128,
        "q_lora_rank": 1024, "kv_lora_rank": 512, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "v_head_dim": 128, "index_n_heads": 64,
        "index_head_dim": 128, "index_topk": 2048,
        "swa_num_attention_heads": 64, "swa_q_lora_rank": 1024,
        "swa_kv_lora_rank": 1024, "swa_qk_nope_head_dim": 192,
        "swa_qk_rope_head_dim": 64, "swa_v_head_dim": 128,
        "sliding_window_size": 513, "num_experts_per_tok": 8,
        "n_shared_experts": 1, "rope_theta": 80000000,
        "swa_rope_theta": 50000, "first_k_dense_replace": 1,
        "max_position_embeddings": 524288, "rms_norm_eps": 1e-05}
    for key, value in published.items():
        assert config[key] == value, key
    assert len(config["layer_types"]) == 46
    assert config["layer_types"].count("full_attention") == 13


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_the_cells_own_metrics_read_nothing_without_a_trace(metric):
    cell = harness.load_cell(CELL, REPO)
    m = next(x for x in cell["per_layer"] if x["name"] == metric)
    # the cell's end-to-end metric is tokens/s: its token gap is one
    # decode step, a third of it host time, too noisy for that bound
    assert m["reader"] == "scope_list" and m["moves"] == "serve_tok_per_s"
    assert json.dumps(m["params"]["scopes"])        # data, in the metric
    read = harness.load_reader(cell, "scope_list").read
    assert read({"host": {}, "trace": None}, m["params"]) is None


def test_the_scope_list_is_data_and_names_the_blocks_operations():
    from tpunet.obs import device_time

    spec = harness.load_json("benchmark", "layer_metrics",
                             "decode_moe_ms.serve.json", root=REPO)
    scope = device_time.classifier([tuple(p) for p in
                                    spec["params"]["scopes"]])
    lm = "jit(_masked_step)/LatentLM/"
    full = lm + "block01/attn/tpunet_mla_full/"
    assert scope(full + "dot_general") == "mla_full"
    assert scope(full + "tpunet_indexer/reduce_sum") == "indexer"
    assert scope(full + "tpunet_kv_select/top_k") == "kv_select"
    assert scope(lm + "block03/attn/tpunet_mla_window/tpunet_window_gather/"
                 "gather") == "mla_window"
    assert scope(lm + "block02/moe/tpunet_moe_router/top_k") == "moe_router"
    assert scope(lm + "block02/moe/tpunet_moe_experts/sort") == "moe_experts"
    assert scope("ragged-dot-none") == "moe_experts"   # the compiler's name
    assert scope(lm + "block02/moe/while/body/tpunet_moe_shared/mul") == \
        "moe_shared"
    assert scope(lm + "block00/tpunet_dense_mlp/dot_general") == "dense_mlp"
    assert scope(lm + "block00/ln2/rsqrt") == "block_other"
    assert scope(lm + "tpunet_head/dot_general") == "head_sample"
    assert scope("jit(_masked_step)/cond/branch_1_fun/reduce_max") == \
        "head_sample"
    assert scope(lm + "embed/jit(_take)/gather") == "embed"
    assert scope(r"cache[\'block01\'][\'attn\'][\'latent\']") == "kv_copy"
    assert scope(r"params[\'block01\'][\'moe\'][\'router\']") == "block_other"
    assert scope("extra[3]") == "step_args"
    assert scope("something_else") is None


def test_the_scope_list_reader_on_the_recorded_trace(monkeypatch, tmp_path):
    """The recorded v5e trace of tests/benchmark/data, read under a
    list the metric brings: a median per execution and a share."""
    import shutil
    import sys

    from tpunet.obs import device_time

    data = os.path.join(REPO, "tests", "benchmark", "data")
    os.makedirs(tmp_path / "trace" / "plugins")
    shutil.copy(os.path.join(data, "probe_v5e.xplane.pb"),
                tmp_path / "trace" / "plugins")
    monkeypatch.setattr(sys, "argv", ["run.py", "--stage", "program",
                                      "--workdir", str(tmp_path)])

    class Holder:
        def program_texts(self):
            with open(os.path.join(data, "probe_v5e.hlo.txt")) as f:
                return {"jit_step": f.read()}

    holder = Holder()
    device_time.register_programs(holder.program_texts)
    reader = harness.load_reader(harness.load_cell(CELL, REPO), "scope_list")
    reader._table.cache_clear()
    scopes = [["flash", "tpunet_flash_fwd"], ["fwd_bwd", "tpunet_fwd_bwd"],
              ["optimizer", "tpunet_optimizer"]]
    ms = reader.read({}, {"scopes": scopes, "program": "jit_step$",
                          "take": ["fwd_bwd", "flash"]})
    assert 0.185 <= ms <= 0.187
    assert reader.read({}, {"scopes": scopes, "program": "/w1$",
                            "take": ["flash"]}) is None   # no such program
    pct = reader.read({}, {"scopes": scopes, "take": "unscoped",
                           "of": "ops"})
    assert 4.3 <= pct <= 4.6
    reader._table.cache_clear()
