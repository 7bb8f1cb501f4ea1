"""The command-a-plus-05-2026 cell at a tiny size: the served tokens are
the float32 reference's best over the vocabulary slice (prefill through
the windowed grouped-query path, decode past the window through the
page pool), a token altered where it is produced comes out as not
correct, the float8 control reads worse than the program, and every new
per-layer metric's reader reads what it should from a synthetic ``obs``
and nothing without a trace, the runner's frame or the program's part."""

import json
import os

import numpy as np
import pytest

import bench_tiny_command_a
import bench_tiny_root
from benchmark import harness, run, trafficgen

CELL = bench_tiny_command_a.CELL
LIMITS = {"served_logit_gap_max": 1e-3, "tokens_missing": 0,
          "failed_requests": 0}
REPO = bench_tiny_root.REPO
SCOPE_METRICS = ("decode_gqa_window_ms.serve", "decode_gqa_global_ms.serve",
                 "prefill_gqa_ms.serve", "decode_moe_shared_ms.serve",
                 "parallel_scope_unattributed_pct.serve")
OWN = SCOPE_METRICS + ("gqa_window_paged_decode_roofline_pct.serve",
                       "window_dead_cache_pct.serve",
                       "prefill_flash_window_roofline_pct.serve")
JOINED = ("decode_moe_ms.serve", "prefill_moe_ms.serve",
          "decode_device_ms.serve_tput", "prefill_device_ms.serve_tput",
          "device_idle_pct.serve", "queue_ms.serve", "slots_busy_pct.serve",
          "peak_hbm_pct.serve", "compiles_in_window.serve",
          "ttft_p95_ms.serve", "idle_unattributed_pct.serve",
          "prefill_rows_per_call.serve", "prefill_useful_tok_pct.serve",
          "decode_live_rows_pct.serve", "decode_wait_ms.serve",
          "idle_admit_pct.serve", "idle_prefill_read_pct.serve",
          "idle_prefix_adopt_pct.serve", "idle_publish_pct.serve",
          "idle_decode_dispatch_pct.serve", "idle_no_traffic_pct.serve",
          "idle_host_other_pct.serve", "publish_ms.serve",
          "prefix_adopt_ms.serve")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = bench_tiny_command_a.make(str(tmp_path_factory.mktemp("bench")))
    bench_tiny_root.set_limits(root, CELL, LIMITS)
    return root


def metric_of(cell, name):
    return next(x for x in cell["per_layer"] if x["name"] == name)


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
        < bench_tiny_command_a.VOCAB
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
        return real(self, (int(token) + 1) % bench_tiny_command_a.VOCAB)

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
            config["vocab_size"]) == (4, 16, 32768)
    assert config["held_experts"] == list(range(16))
    assert config["layer_types"][:4] == ["sliding_attention"] * 3 \
        + ["full_attention"]
    assert {k: traffic[k] for k in (
        "arrival", "clients", "pool_prompt", "pool_output", "tokens",
        "greedy", "fill_seconds", "trace_seconds", "sample_requests",
        "request_timeout_s", "max_requests", "order_seed")} == {
        "arrival": "closed", "clients": 16, "pool_prompt": 4,
        "pool_output": 4, "tokens": "uniform", "greedy": True,
        "fill_seconds": 20, "trace_seconds": 12, "sample_requests": 6,
        "request_timeout_s": 600, "max_requests": 1024, "order_seed": 39}
    assert traffic["prompt_len"] == {"dist": "lognormal", "median": 6144,
                                     "sigma": 0.3, "min": 4096, "max": 8192}
    assert traffic["output_len"] == {"dist": "lognormal", "median": 768,
                                     "sigma": 0.7, "min": 256, "max": 2048}
    lengths = trafficgen.length_pool(traffic)
    assert sorted({p for p, _ in lengths}) == [4351, 5584, 6760, 8192]
    assert sorted({o for _, o in lengths}) == [343, 614, 960, 1718]
    assert min(p for p, _ in lengths) > config["sliding_window"]
    serve = cell["cell"]["program"]["serve"]
    assert serve["slots"] == traffic["clients"] == 16
    assert serve["prefill_buckets"] == [8192]
    assert (serve["queue_max"], serve["max_new_tokens_cap"]) == (64, 2048)
    assert set(serve) == {"slots", "prefill_buckets", "queue_max",
                          "emit_every_s", "max_new_tokens_cap"}
    model = config["program"]["model"]
    assert model["max_seq_len"] == 10240 >= max(p + o for p, o in lengths)
    reqs = trafficgen.serve_requests(traffic, config, 3000000019, 16)
    assert max(int(r["prompt"].max()) for r in reqs) < config["vocab_size"]
    # the program's sizes are the configuration's, key for key
    for key, value in model["latent"].items():
        if key == "layer_types":
            assert value == config["layer_types"][:4]
        elif key == "num_experts":
            assert value == config["num_experts_published"] == 128
        else:
            assert value == config[key], key
    names = {m["name"] for m in cell["per_layer"]}
    assert names >= set(OWN) | set(JOINED)
    assert "itl_p95_ms" not in {m["name"] for m in cell["end_to_end"]}
    for name in OWN:
        m = metric_of(cell, name)
        assert m["workloads"] == [CELL] and m["moves"] == "serve_tok_per_s"


def test_the_published_keys_are_the_catalogs():
    """Every key of the configuration that is not in ``reduced`` (or
    added beside the published ones) reads as the public config does —
    kept as data here: the widths no PR may cut."""
    config = harness.load_cell(CELL, REPO)["config"]
    published = {
        "attention_bias": False, "expert_selection_fn": "sigmoid",
        "first_k_dense_replace": 0, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 4096, "intermediate_size": 4096,
        "layer_norm_eps": 1e-05, "layer_switch": 4, "logit_scale": 1,
        "max_position_embeddings": 200000, "model_type": "cohere2_moe",
        "norm_topk_prob": True, "num_attention_heads": 128,
        "num_experts_per_tok": 8, "num_key_value_heads": 8,
        "num_shared_experts": 4,
        "order_of_interleaved_layers": "local_attn_first",
        "position_embedding_type": "rope_gptj",
        "prefix_dense_intermediate_size": 16384,
        "prefix_dense_sliding_window_pattern": 1, "rms_norm_eps": None,
        "rope_parameters": {"rope_theta": 50000, "rope_type": "default"},
        "rope_theta": 50000, "rotary_pct": 1,
        "shared_expert_combination_strategy": "average",
        "sliding_window": 4096, "tf_legacy_loss": False,
        "tie_word_embeddings": True, "use_embedding_sharing": True,
        "use_gated_activation": True, "use_parallel_block": True,
        "use_parallel_embedding": False, "use_qk_norm": False,
        "num_hidden_layers_published": 32, "num_experts_published": 128,
        "vocab_size_published": 262144}
    for key, value in published.items():
        assert config[key] == value, key
    assert len(config["layer_types"]) == 32
    assert config["layer_types"] == (["sliding_attention"] * 3
                                     + ["full_attention"]) * 8
    assert "8 chips share each layer" in config["deployment"]
    assert "64 chips" in config["deployment"]
    assert len(config["assumed"]) >= 7


def test_the_parameters_are_the_issues_count():
    """4,733,292,544 parameters = 9.47 GB of bfloat16: the cut's
    arithmetic, from the reference's own spec."""
    import math

    cell = harness.load_cell(CELL, REPO)
    spec = harness.load_reference(cell).param_spec(cell["config"], "serve")
    per = lambda key: sum(math.prod(s) for p, (s, _, _) in spec.items()  # noqa: E731
                          if key in p)
    assert per("block00/attn") == 142_606_336
    assert per("block00/moe/shared") == 201_326_592
    assert per("block00/moe/experts") == 805_306_368
    assert per("block00/") == 1_149_767_680
    assert sum(math.prod(s) for s, _, _ in spec.values()) == 4_733_292_544


def test_the_reference_imports_nothing_of_the_program():
    path = os.path.join(REPO, "benchmark", "reference",
                        "command-a-plus-05-2026.py")
    text = open(path).read()
    assert "tpunet" not in text.split('"""', 2)[2]


@pytest.mark.parametrize("metric", SCOPE_METRICS)
def test_the_cells_own_scope_metrics_read_nothing_without_a_trace(metric):
    cell = harness.load_cell(CELL, REPO)
    m = metric_of(cell, metric)
    assert m["reader"] == "scope_list"
    assert json.dumps(m["params"]["scopes"])        # data, in the metric
    read = harness.load_reader(cell, "scope_list").read
    assert read({"host": {}, "trace": None}, m["params"]) is None


def test_the_scope_list_names_the_blocks_operations():
    from tpunet.obs import device_time

    cell = harness.load_cell(CELL, REPO)
    lists = {json.dumps(m["params"]["scopes"]) for m in cell["per_layer"]
             if m["name"] in SCOPE_METRICS}
    assert len(lists) == 1                          # one table a traced run
    classify = device_time.classifier(
        [tuple(p) for p in json.loads(lists.pop())])
    step = "jit(_masked_step)/LatentLM/block"
    for path, label in (
            (step + "01/attn/tpunet_gqa_window/tpunet_paged_decode_fwd/"
             "pallas_call", "gqa_window"),
            (step + "02/attn/tpunet_gqa_window/tpunet_flash_fwd/pallas_call",
             "gqa_window"),
            (step + "03/attn/tpunet_gqa_full/dot_general", "gqa_full"),
            (step + "03/moe/tpunet_moe_shared/dot", "moe_shared"),
            (step + "00/moe/tpunet_moe_router/top_k", "moe_router"),
            ("ragged-dot-7", "moe_experts"),
            (step + "01/add", "block_other"),
            ("jit(_masked_step)/LatentLM/tpunet_head/dot", "head_sample"),
            ("cache['block00']['attn']['cached_k']", "kv_copy")):
        assert classify(path) == label, path
    # the wide programs, not the width-1 one, and the reverse
    import re
    wide = re.compile(metric_of(cell, "prefill_gqa_ms.serve")["params"][
        "program"])
    one = re.compile(metric_of(cell, "decode_gqa_window_ms.serve")["params"][
        "program"])
    assert wide.search("step/w8192") and not wide.search("step/w1")
    assert one.search("step/w1") and not one.search("step/w8192")


def test_the_window_dead_share_reads_the_engines_gauge():
    """The reader walks to the runner's frame for ``engine``; a program
    without the gauge (the parent commit) or a call from elsewhere reads
    nothing and raises nothing."""
    from tpunet.obs.registry import Registry

    cell = harness.load_cell(CELL, REPO)
    m = metric_of(cell, "window_dead_cache_pct.serve")
    assert (m["unit"], m["layer"], m["source"]) == ("%", "device memory",
                                                    "program_counter")
    read = harness.load_reader(cell, m["reader"]).read
    obs = {"cell": cell}
    assert read(obs, m["params"]) is None           # no runner's frame

    class FakeEngine:
        registry = Registry()

    def program(gauge=None):
        engine, load, t_open, t_close = FakeEngine(), None, 0.0, 1.0  # noqa: F841
        if gauge is not None:
            engine.registry.gauge("serve_cache_window_dead_pct").set(gauge)
        return read(obs, m["params"])

    assert program() is None                        # no such gauge
    assert program(gauge=31.25) == 31.25


class _Load:
    requests = [{"prompt": [0] * 100}, {"prompt": [0] * 5000}]
    records = [{"index": 0, "token_t": [0.5, 1.5, 2.5, 9.0]},
               {"index": 1, "token_t": [1.2, 1.8]}]


def _trace(op, n=2, dur=2e-6):
    from benchmark import trace_reduce

    return trace_reduce.Trace(device_ops={"/device:TPU:0": [
        (f"%{op}.{i} = bf16[] custom-call()", 1.0 + i, dur)
        for i in range(n)] + [("%fusion.1 = f32[] fusion()", 9.0, 1.0)]})


def test_the_windowed_kernels_roofline_share_counts_keys_in_sight():
    """Bytes and operations from shapes (``opcount_gqa_window``), the
    contexts from the runner's own records: two requests, three
    decode-produced tokens inside the window, one of them past the
    4096-key window. Nothing to read without a trace, the frame, the
    kernel, or a configuration with a window."""
    from benchmark import opcount_gqa_window as oc

    cell = harness.load_cell(CELL, REPO)
    m = metric_of(cell, "gqa_window_paged_decode_roofline_pct.serve")
    assert (m["unit"], m["layer"]) == ("%", "kernels")
    read = harness.load_reader(cell, m["reader"]).read
    config = cell["config"]
    assert oc.layer_kinds(config) == (3, 1)
    count = oc.paged_decode([101, 102, 5001], config)
    keys = 3 * (101 + 102 + 4096) + (101 + 102 + 5001)
    assert count == {"bytes": keys * 2 * 1024 * 2 + 3 * 4 * 2 * 16384 * 2,
                     "flops": keys * 4 * 16384, "rows": 3, "keys": keys}
    peaks = harness.peaks_for("TPU v5 lite")
    assert oc.roofline_seconds(count, peaks) == count["bytes"] / 819e9

    def program(tr, cell_=cell):
        load, t_open, t_close, engine = _Load(), 1.0, 3.0, None  # noqa: F841
        return read({"trace": tr, "cell": cell_,
                     "device_kind": "TPU v5 lite"}, m["params"])

    got = program(_trace("tpunet_paged_decode"))
    assert got == pytest.approx(100 * count["bytes"] / 819e9 / 4e-6)
    assert program(_trace("other_kernel")) is None
    assert program(None) is None
    other = dict(cell, config={k: v for k, v in config.items()
                               if k != "sliding_window"})
    assert program(_trace("tpunet_paged_decode"), other) is None
    assert read({"trace": _trace("tpunet_paged_decode"), "cell": cell,
                 "device_kind": "TPU v5 lite"}, m["params"]) is None


def test_the_flash_kernels_roofline_share_counts_the_band():
    """Per bucket-wide execution one kernel a layer: eight operations
    are two calls; the pairs a query may see are the window's band in
    three layers and the triangle in the fourth."""
    from benchmark import opcount_gqa_window as oc

    cell = harness.load_cell(CELL, REPO)
    m = metric_of(cell, "prefill_flash_window_roofline_pct.serve")
    read = harness.load_reader(cell, m["reader"]).read
    config = cell["config"]
    count = oc.flash_prefill(8192, config)
    band = 4096 * 4097 // 2 + 4096 * 4096
    assert count["pairs"] == 3 * band + 8192 * 8193 // 2
    assert count["flops"] == count["pairs"] * 4 * 16384
    assert oc.flash_prefill(100, config)["pairs"] == 4 * 100 * 101 // 2
    obs = {"cell": cell, "device_kind": "TPU v5 lite"}
    got = read(dict(obs, trace=_trace("tpunet_flash_fwd", 8, 0.01)),
               m["params"])
    assert got == pytest.approx(100 * 2 * count["flops"] / 197e12 / 0.08)
    assert 0 < got <= 100
    assert read(dict(obs, trace=_trace("other", 8)), m["params"]) is None
    assert read(dict(obs, trace=None), m["params"]) is None
    two = json.loads(json.dumps(cell))
    two["cell"]["program"]["serve"]["prefill_buckets"] = [4096, 8192]
    assert read({"cell": two, "device_kind": "TPU v5 lite",
                 "trace": _trace("tpunet_flash_fwd", 8)},
                m["params"]) is None
