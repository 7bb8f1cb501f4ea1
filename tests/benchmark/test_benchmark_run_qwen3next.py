"""The qwen3-next-80b-a3b cell at a tiny size: the served tokens are the
float32 reference's best over the vocabulary slice (prefill through the
chunked delta rule, decode through the state pool, the reference token
by token), a token altered where it is produced comes out as not
correct, the float8 control reads worse than the program, and the
cell's own per-layer metrics are left out without a trace."""

import json
import os

import numpy as np
import pytest

import bench_tiny_qwen3next
import bench_tiny_root
from benchmark import harness, run, trafficgen

CELL = bench_tiny_qwen3next.CELL
LIMITS = {"served_logit_gap_max": 1e-3, "tokens_missing": 0,
          "failed_requests": 0}
REPO = bench_tiny_root.REPO
SCOPE_METRICS = ("decode_gdn_ms.serve", "prefill_gdn_ms.serve",
                 "prefill_gdn_scan_ms.serve", "decode_gqa_attn_ms.serve",
                 "hybrid_scope_unattributed_pct.serve")
JOINED = ("decode_moe_ms.serve", "decode_device_ms.serve_tput",
          "prefill_device_ms.serve_tput", "device_idle_pct.serve",
          "queue_ms.serve", "slots_busy_pct.serve", "peak_hbm_pct.serve",
          "compiles_in_window.serve", "ttft_p95_ms.serve",
          "idle_unattributed_pct.serve", "prefill_rows_per_call.serve",
          "prefill_useful_tok_pct.serve", "decode_live_rows_pct.serve",
          "decode_wait_ms.serve")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = bench_tiny_qwen3next.make(str(tmp_path_factory.mktemp("bench")))
    bench_tiny_root.set_limits(root, CELL, LIMITS)
    return root


def test_closed_loop_is_correct_over_the_vocabulary_slice(root, tmp_path):
    ctx = bench_tiny_root.context(root, CELL, str(tmp_path), control="fp8")
    prog = run.run_stage(ctx, "program")
    ref = run.run_stage(ctx, "reference")
    assert ref["correct"] is True and ref["compared_tokens"] >= 6
    assert prog["attempted"] > 0 and prog["failed"] == 0
    assert prog["numbers"]["tokens_missing"] == 0
    assert (ref["control"]["served_logit_gap_max"]
            > 10 * ref["numbers"]["served_logit_gap_max"])
    cap = np.load(os.path.join(str(tmp_path), "capture.npz"))
    assert max(int(cap[k].max()) for k in cap.files) \
        < bench_tiny_qwen3next.VOCAB
    line = run.final_line(ctx["cell"], False, prog, ref)
    assert line["metrics"] == {}                    # nothing timed off the TPU
    assert set(prog["metrics"]) == {"serve_tok_per_s", "itl_p95_ms",
                                    "setup_s"}
    assert {m["name"] for m in ctx["cell"]["end_to_end"]} == {
        "serve_tok_per_s", "setup_s"}


def test_a_token_altered_where_it_is_produced_is_not_correct(
        root, tmp_path, monkeypatch):
    from tpunet.serve.scheduler import GenerateRequest

    real = GenerateRequest.push_token

    def altered(self, token):
        return real(self, (int(token) + 1) % bench_tiny_qwen3next.VOCAB)

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
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (8, 128, 37984)
    assert config["held_experts"] == list(range(128))
    assert config["layer_types"][:8] == (["linear_attention"] * 3
                                         + ["full_attention"]) * 2
    lengths = trafficgen.length_pool(traffic)
    assert sorted({p for p, _ in lengths}) == [4351, 5584, 6760, 8192]
    assert sorted({o for _, o in lengths}) == [343, 614, 960, 1718]
    serve = cell["cell"]["program"]["serve"]
    assert serve["slots"] == traffic["clients"] == 32
    assert serve["prefill_buckets"] == [8192]
    assert set(serve) == {"slots", "prefill_buckets", "queue_max",
                          "emit_every_s", "max_new_tokens_cap"}
    model = config["program"]["model"]
    assert model["max_seq_len"] == 10240 >= max(p + o for p, o in lengths)
    reqs = trafficgen.serve_requests(traffic, config, 3000000019, 16)
    assert max(int(r["prompt"].max()) for r in reqs) < config["vocab_size"]
    # the program's sizes are the configuration's, key for key
    for key, value in model["latent"].items():
        if key == "layer_types":
            assert value == config["layer_types"][:8]
        elif key == "num_experts":
            assert value == config["num_experts_published"] == 512
        elif key == "first_k_dense_replace":
            assert value == 0 and config["mlp_only_layers"] == []
        else:
            assert value == config[key], key
    names = {m["name"] for m in cell["per_layer"]}
    assert names >= set(SCOPE_METRICS) | set(JOINED) | {
        "state_pool_hbm_pct.serve"}
    assert "itl_p95_ms" not in {m["name"] for m in cell["end_to_end"]}


def test_the_published_keys_are_the_catalogs():
    """Every key of the configuration that is not in ``reduced`` (or
    added beside the published ones) reads as the public config does —
    kept as data here: the widths this PR may never cut."""
    config = harness.load_cell(CELL, REPO)["config"]
    published = {
        "decoder_sparse_step": 1, "full_attention_interval": 4,
        "head_dim": 256, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 5120, "linear_conv_kernel_dim": 4,
        "linear_key_head_dim": 128, "linear_num_key_heads": 16,
        "linear_num_value_heads": 32, "linear_value_head_dim": 128,
        "max_position_embeddings": 262144, "mlp_only_layers": [],
        "model_type": "qwen3_next", "moe_intermediate_size": 512,
        "norm_topk_prob": True, "num_attention_heads": 16,
        "num_experts_per_tok": 10, "num_key_value_heads": 2,
        "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 10000000,
        "shared_expert_intermediate_size": 512,
        "tie_word_embeddings": False, "use_sliding_window": False,
        "num_hidden_layers_published": 48, "num_experts_published": 512,
        "vocab_size_published": 151936}
    for key, value in published.items():
        assert config[key] == value, key
    assert len(config["layer_types"]) == 48
    assert config["layer_types"].count("full_attention") == 12
    assert "4 chips share each layer" in config["deployment"]


def test_the_parameters_are_the_issues_count():
    """3,667,251,328 parameters = 7.33 GB of bfloat16: the cut's
    arithmetic, from the reference's own spec."""
    import math

    cell = harness.load_cell(CELL, REPO)
    spec = harness.load_reference(cell).param_spec(cell["config"], "serve")
    assert sum(math.prod(s) for s, _, _ in spec.values()) == 3_667_251_328


@pytest.mark.parametrize("metric", SCOPE_METRICS)
def test_the_cells_own_metrics_read_nothing_without_a_trace(metric):
    cell = harness.load_cell(CELL, REPO)
    m = next(x for x in cell["per_layer"] if x["name"] == metric)
    assert m["reader"] == "scope_list" and m["moves"] == "serve_tok_per_s"
    assert m["workloads"] == [CELL]
    assert json.dumps(m["params"]["scopes"])        # data, in the metric
    read = harness.load_reader(cell, "scope_list").read
    assert read({"host": {}, "trace": None}, m["params"]) is None


def test_the_scope_list_names_the_mixers_operations():
    from tpunet.obs import device_time

    cell = harness.load_cell(CELL, REPO)
    lists = {json.dumps(m["params"]["scopes"]) for m in cell["per_layer"]
             if m["name"] in SCOPE_METRICS}
    assert len(lists) == 1                          # one table a traced run
    classify = device_time.classifier(
        [tuple(p) for p in json.loads(lists.pop())])
    step = "jit(_masked_step)/LatentLM/block02/linear_attn/tpunet_gdn/"
    for path, label in (
            (step + "tpunet_gdn_scan/mul", "gdn_scan"),
            (step + "dot_general", "gdn"),
            ("jit(_masked_step)/LatentLM/block03/attn/tpunet_gqa_full/"
             "tpunet_paged_decode_fwd/pallas_call", "gqa_full"),
            ("jit(_masked_step)/LatentLM/block03/moe/tpunet_moe_shared/dot",
             "moe_shared"),
            ("ragged-dot-7", "moe_experts"),
            ("jit(_masked_step)/LatentLM/block01/add", "block_other"),
            ("jit(_masked_step)/LatentLM/tpunet_head/dot", "head_sample")):
        assert classify(path) == label, path


def test_the_state_pools_share_reads_the_engines_gauge():
    """The reader walks to the runner's frame for ``engine`` and
    ``stats``; a program without the gauge (the parent commit) or a call
    from elsewhere reads nothing and raises nothing."""
    from tpunet.obs.registry import Registry

    cell = harness.load_cell(CELL, REPO)
    m = next(x for x in cell["per_layer"]
             if x["name"] == "state_pool_hbm_pct.serve")
    read = harness.load_reader(cell, m["reader"]).read
    obs = {"cell": cell}
    assert read(obs, m["params"]) is None           # no runner's frame

    class FakeEngine:
        registry = Registry()

    def program(stats, gauge=None):
        engine, load, t_open, t_close = FakeEngine(), None, 0.0, 1.0  # noqa: F841
        if gauge is not None:
            engine.registry.gauge("serve_state_pool_bytes").set(gauge)
        return read(obs, m["params"])

    assert program({"bytes_limit": 1000}) is None   # no such gauge
    assert program({"bytes_limit": 1000}, gauge=250) == 25.0
    assert program({}, gauge=250) is None


def test_the_paged_decode_kernels_roofline_share_counts_live_keys():
    """Bytes and operations from shapes (``opcount_hybrid``), the keys
    from the runner's own records: two requests, three decode-produced
    tokens inside the window; the kernel's operations averaged over the
    devices. Nothing to read without a trace, the frame or the kernel."""
    from benchmark import opcount_hybrid, trace_reduce

    cell = harness.load_cell(CELL, REPO)
    m = next(x for x in cell["per_layer"]
             if x["name"] == "paged_decode_roofline_pct.serve")
    assert (m["unit"], m["layer"], m["moves"]) == ("%", "kernels",
                                                   "serve_tok_per_s")
    read = harness.load_reader(cell, m["reader"]).read
    config = cell["config"]
    count = opcount_hybrid.paged_decode_gqa(1000, 2, config)
    assert count == {"bytes": 1000 * 2 * 512 * 2 + 2 * 2 * 4096 * 2,
                     "flops": 1000 * 4 * 4096}
    assert opcount_hybrid.full_attention_layers(config) == 2
    peaks = harness.peaks_for("TPU v5 lite")
    assert opcount_hybrid.roofline_seconds(count, peaks) \
        == count["bytes"] / 819e9                  # bound by bytes

    class Load:
        requests = [{"prompt": [0] * 100}, {"prompt": [0] * 300}]
        records = [{"index": 0, "token_t": [0.5, 1.5, 2.5, 9.0]},
                   {"index": 1, "token_t": [1.2, 1.8]}]

    def trace(op):
        return trace_reduce.Trace(device_ops={"/device:TPU:0": [
            (f"%{op}.3 = bf16[] custom-call()", 1.0, 2e-6),
            (f"%{op}.4 = bf16[] custom-call()", 2.0, 2e-6),
            ("%fusion.1 = f32[] fusion()", 3.0, 1.0)]})

    def program(tr):
        load, t_open, t_close, engine = Load(), 1.0, 3.0, None  # noqa: F841
        return read({"trace": tr, "cell": cell,
                     "device_kind": "TPU v5 lite"}, m["params"])

    # tokens 1 and 2 of the first request (101, 102 keys), token 1 of
    # the second (301): 504 live keys over 3 rows, two layers
    want = opcount_hybrid.paged_decode_gqa(504, 3, config)
    got = program(trace("tpunet_paged_decode"))
    assert got == pytest.approx(100 * 2 * want["bytes"] / 819e9 / 4e-6)
    assert program(trace("other_kernel")) is None
    assert program(None) is None
    assert read({"trace": trace("tpunet_paged_decode"), "cell": cell,
                 "device_kind": "TPU v5 lite"}, m["params"]) is None
