"""The device's time by the program's own scopes: the scope table from
HLO text, the trace x table reduction on the trace recorded on a v5e
(``data/probe_v5e.xplane.pb``; ``data/probe_v5e.hlo.txt`` is that
step's optimized HLO, printed from the module the trace itself carries
in its ``/host:metadata`` plane), the lazy table of programs, and the
readers that report it — each on hand-built input, each ``None``
without a trace."""

import gc
import os
import shutil
import sys

import pytest

import bench_tiny_root
from benchmark import harness, scope_time, trace_reduce
from tpunet.obs import device_time, hlo_bytes

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
XPLANE = os.path.join(DATA, "probe_v5e.xplane.pb")
CELL = harness.load_cell("gpt2-xl.serve-closed16", bench_tiny_root.REPO)

SMALL = """HloModule jit_f, is_scheduled=true

%fused_computation (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  %mul.1 = f32[8]{0} multiply(%p0, %p0), metadata={op_name="jit(f)/tpunet_fwd_bwd/M/block00/attn/mul"}
  ROOT %add.1 = f32[8]{0} add(%mul.1, %p0), metadata={op_name="jit(f)/tpunet_fwd_bwd/M/block00/attn/qkv/add"}
}

%fused_computation.1 (p1: f32[8]) -> f32[8] {
  %p1 = f32[8]{0} parameter(0)
  ROOT %neg.1 = f32[8]{0} negate(%p1), metadata={op_name="jit(f)/tpunet_fwd_bwd/M/block00/mlp/neg"}
}

%body (t: (s32[], f32[8])) -> (s32[], f32[8]) {
  %t = (s32[], f32[8]{0}) parameter(0)
  %g = f32[8]{0} get-tuple-element(%t), index=1
  %exp.7 = f32[8]{0} exponential(%g), metadata={op_name="jit(f)/tpunet_optimizer/while/body/exp"}
  ROOT %tup = (s32[], f32[8]{0}) tuple(%g, %exp.7)
}

ENTRY %main.9 (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0), metadata={op_name="x"}
  %fusion.59 = f32[8]{0} fusion(%x), kind=kLoop, calls=%fused_computation
  %fusion.60 = f32[8]{0} fusion(%fusion.59), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(f)/tpunet_fwd_bwd/M/block00/mlp/fc1/dot_general"}
  %copy.467 = f32[8]{0} copy(%fusion.60)
  %iota.3 = s32[8]{0} iota(), iota_dimension=0
  %while.1 = (s32[], f32[8]{0}) while(%copy.467), condition=%cond, body=%body
  ROOT %out = f32[8]{0} get-tuple-element(%while.1), index=1
}
"""


def test_a_fusion_without_its_own_name_takes_its_instructions_common_scope():
    scopes = hlo_bytes.op_scopes(SMALL)
    assert scopes["fusion.59"] == "jit(f)/tpunet_fwd_bwd/M/block00/attn"
    assert scopes["fusion.60"].endswith("/mlp/fc1/dot_general")   # its own
    # unnamed by the compiler: its first named operand's scope, else none
    assert scopes["copy.467"] == scopes["fusion.60"]
    assert scopes["iota.3"] == "" and scopes["x"] == "x"
    assert scopes["exp.7"].endswith("while/body/exp")   # a called body
    assert "mul.1" not in scopes and "neg.1" not in scopes  # inside fusions
    assert hlo_bytes.op_scopes("") == {}


def test_scope_table_of_the_recorded_step():
    with open(os.path.join(DATA, "probe_v5e.hlo.txt")) as f:
        scopes = hlo_bytes.op_scopes(f.read())
    assert scopes["convolution_tanh_fusion"] == \
        "jit(step)/tpunet_fwd_bwd/dot_general"
    assert scopes["fusion.1"].startswith(
        "jit(step)/tpunet_fwd_bwd/tpunet_flash_fwd/")
    assert scopes["copy.1"] == "jit(step)/tpunet_optimizer/add"
    assert scopes["slice-done.2"] == scopes["slice-start.2"] == "x"
    assert len(scopes) == 21


def _probe_text():
    with open(os.path.join(DATA, "probe_v5e.hlo.txt")) as f:
        return f.read()


def test_device_time_by_scope_on_the_recorded_trace():
    scopes = [("flash", "tpunet_flash_fwd"), ("fwd_bwd", "tpunet_fwd_bwd"),
              ("optimizer", "tpunet_optimizer")]
    table = device_time.device_time_by_scope(
        XPLANE, {"jit_step": _probe_text()}, scopes)
    assert list(table) == ["jit_step"]
    runs = table["jit_step"]["executions"]
    assert [e["run_id"] for e in runs] == [6, 7, 8, 9, 10, 11]
    for e in runs:
        assert 216e-6 < e["device_s"] < 217e-6
        assert e["op_s"] <= e["device_s"]             # own times, no overlap
        assert e["op_s"] > 0.999 * e["device_s"]
        assert sum(e["by_scope"].values()) + e["unscoped_s"] == \
            pytest.approx(e["op_s"])
        # first match wins: the flash scope lies inside tpunet_fwd_bwd
        assert 90e-6 < e["by_scope"]["fwd_bwd"] < 93e-6
        assert 92e-6 < e["by_scope"]["flash"] < 96e-6
        assert 20e-6 < e["by_scope"]["optimizer"] < 23e-6
        assert 9e-6 < e["unscoped_s"] < 10e-6        # copies of argument x
    ops = table["jit_step"]["ops"]
    assert ops[("copy", "x")] == pytest.approx(57.3e-6, rel=0.01)
    assert ("slice-done.3", "x") in ops


def test_programs_are_told_apart_by_shapes_and_unknown_ones_keep_their_name():
    text = _probe_text()
    other_width = text.replace("2048,2048", "1024,1024")
    table = device_time.device_time_by_scope(
        XPLANE, {"jit_step/w1024": other_width, "jit_step/w2048": text,
                 "jit_other": text.replace("HloModule jit_step",
                                           "HloModule jit_other")},
        hlo_bytes.phase_of)
    assert list(table) == ["jit_step/w2048"]
    assert len(table["jit_step/w2048"]["executions"]) == 6
    table = device_time.device_time_by_scope(XPLANE, {}, hlo_bytes.phase_of)
    (label,) = table
    assert label.startswith("jit_step(") and label.endswith(")")
    assert all(e["unscoped_s"] == pytest.approx(e["op_s"]) and not
               e["by_scope"] for e in table[label]["executions"])


def test_own_time_goes_to_the_operation_that_started_last():
    own = device_time._self_times(
        [(0, 10, "while"), (1, 2, "a"), (4, 3, "b"), (6, 6, "c"),
         (20, 1, "z")])
    assert [(n, t) for _, t, n in own] == [
        ("while", 2.0), ("a", 2.0), ("b", 2.0), ("c", 6.0), ("z", 1.0)]


def test_phase_table_from_a_trace_directory_with_jax_alone(tmp_path):
    sys.path.insert(0, os.path.join(bench_tiny_root.REPO, "scripts"))
    import obs_report

    phases, notes = obs_report.device_phases(DATA)
    assert notes == [] and list(phases)[0] == "fwd"
    assert 85.0 < phases["fwd"]["pct"] < 86.5
    assert 9.0 < phases["optimizer"]["pct"] < 10.5
    assert any("fwd" in line for line in obs_report.render_phases(phases))
    shutil.copy(XPLANE, tmp_path)                # a trace, no program text
    phases, notes = obs_report.device_phases(str(tmp_path))
    assert phases is None and "no program text" in notes[1]


# ------------------------------------------------------------ the lazy table

class _Holder:
    def __init__(self, texts):
        self.texts, self.calls = texts, 0

    def program_texts(self):
        self.calls += 1
        return self.texts


@pytest.fixture
def providers(monkeypatch):
    monkeypatch.setattr(device_time, "_PROVIDERS", [])
    return device_time._PROVIDERS


def test_the_table_is_lazy_and_keeps_nothing_alive(providers, tmp_path):
    a, b = _Holder({"jit_a": "HloModule jit_a"}), _Holder({"jit_b/w1": "x"})
    device_time.register_programs(a.program_texts)
    device_time.register_programs(b.program_texts)
    assert a.calls == b.calls == 0
    assert device_time.program_texts() == {"jit_a": "HloModule jit_a",
                                           "jit_b/w1": "x"}
    assert sorted(os.path.basename(p) for p in
                  device_time.write_program_texts(str(tmp_path))) == [
        "jit_a.hlo.txt", "jit_b.w1.hlo.txt"]
    del b
    gc.collect()
    assert device_time.program_texts() == {"jit_a": "HloModule jit_a"}
    assert len(providers) == 1
    assert device_time.module_name("HloModule jit_a, x=1") == "jit_a"


def _raises(self):
    raise AssertionError("the table was evaluated")


def test_building_an_engine_does_not_evaluate_its_programs(
        providers, monkeypatch, tmp_path):
    from tpunet.serve.engine import Engine

    root = bench_tiny_root.make(str(tmp_path))
    cell = harness.load_cell("gpt2-xl.serve-closed16", root)
    build = harness.load_runner(cell).build_engine
    monkeypatch.setattr(Engine, "program_texts", _raises)
    engine = build(cell, 11)                      # must not raise
    assert len(providers) == 1
    with pytest.raises(AssertionError, match="evaluated"):
        device_time.program_texts()
    monkeypatch.undo()
    texts = engine.program_texts()
    assert sorted(texts) == ["jit__masked_step/w1", "jit__masked_step/w32",
                             "jit__masked_step/w8"]
    classify = device_time.classifier(scope_time.scopes_for("serve"))
    found = {classify(p) for p in
             hlo_bytes.op_scopes(texts["jit__masked_step/w1"]).values() if p}
    assert {"attn", "mlp", "head_sample"} <= found
    assert device_time.module_name(texts["jit__masked_step/w8"]) == \
        "jit__masked_step"


def test_building_a_trainer_does_not_evaluate_its_program(
        providers, monkeypatch, tmp_path):
    from benchmark import trafficgen
    from tpunet.train.loop import Trainer
    from tpunet.utils.cache import (_COMPILES,
                                    enable_persistent_compile_cache)

    root = bench_tiny_root.make(str(tmp_path))
    cell = harness.load_cell("gpt2-xl.train-b8-t1024", root)
    runner = harness.load_runner(cell)
    data = trafficgen.train_data(cell["traffic"], cell["config"], 5)
    monkeypatch.setattr(Trainer, "program_texts", _raises)
    trainer = Trainer(runner.build_config(cell, 5, str(tmp_path)),
                      dataset=data)               # must not raise
    try:
        assert len(providers) == 1
        monkeypatch.undo()
        trainer.train_one_epoch(0)
        enable_persistent_compile_cache()         # counts compiles; idempotent
        compiled = _COMPILES["programs"]
        (text,) = trainer.program_texts().values()
        assert _COMPILES["programs"] == compiled  # the running step's own
        phases = {hlo_bytes.phase_of(p)
                  for p in hlo_bytes.op_scopes(text).values()}
        assert {"fwd", "bwd", "optimizer"} <= phases
    finally:
        trainer.close()


def test_no_new_obs_symbol_on_the_step_paths():
    from tpunet.serve.engine import Engine
    from tpunet.serve.scheduler import GenerateRequest
    from tpunet.train.loop import Trainer

    hot = [Engine._iterate, Engine._reap, Engine._admit, Engine._prefill,
           Engine._decode_width1, Engine._dispatch_step,
           Engine._slot_maybe_finish, GenerateRequest.push_token,
           Trainer.train_one_epoch]
    for fn in hot:
        names = set(fn.__code__.co_names)
        assert not names & {"device_time", "program_texts",
                            "register_programs", "op_scopes"}, fn


# ------------------------------------------------------------------ readers

def _table():
    def run(device, **by_scope):
        return {"run_id": 1, "start_s": 0.0, "device_s": device,
                "op_s": sum(by_scope.values()) + 0.001,
                "by_scope": by_scope, "unscoped_s": 0.001}
    return {
        "jit__masked_step/w1": {"ops": {}, "executions": [
            run(0.140, attn=0.130, mlp=0.005, head_sample=0.001),
            run(0.142, attn=0.132, mlp=0.006, head_sample=0.001),
            run(0.020, attn=0.010)]},                 # cut by the trace's edge
        "jit__masked_step/w128": {"ops": {}, "executions": [
            run(0.200, attn=0.1)]},
        "jit__masked_step/w512": {"ops": {}, "executions": [
            run(0.630, attn=0.3), run(0.640, attn=0.3)]},
        "jit_page_copy(77)": {"ops": {}, "executions": [run(0.004)]},
        "": {"ops": {}, "executions": [run(0.0)]}}


def test_medians_and_shares_of_a_scope_table():
    tab = _table()
    assert scope_time.median_ms(tab, "/w1$", "device") == pytest.approx(140.0)
    assert scope_time.median_ms(tab, r"/w(?!1$)\d+$", "device") == \
        pytest.approx(630.0)
    assert scope_time.median_ms(tab, "/w1$", ["attn"]) == pytest.approx(130.0)
    assert scope_time.median_ms(tab, "/w1$", ["attn", "kv_copy"]) == \
        pytest.approx(130.0)
    assert scope_time.median_ms(tab, "/w1$", ["mlp", "head_sample"]) == \
        pytest.approx(6.0)
    assert scope_time.median_ms(tab, "^[^(]+$", "device") == \
        pytest.approx(171.0)                       # the table's own programs
    assert scope_time.median_ms(tab, "/w9$", "device") is None
    ops = sum(e["op_s"] for p in tab.values() for e in p["executions"])
    assert scope_time.share_pct(tab, "unscoped", "ops") == \
        pytest.approx(100.0 * 0.008 / ops)
    assert scope_time.share_pct(tab, ["mlp"], ["mlp", "head_sample"]) == \
        pytest.approx(100.0 * 0.011 / 0.013)
    assert scope_time.share_pct({}, "unscoped", "ops") is None


def test_scope_families_are_data():
    serve = device_time.classifier(scope_time.scopes_for("serve"))
    lm = "jit(_masked_step)/TransformerLM/"
    assert serve(lm + "block07/attn/attn._decode_attend/dot_general") == "attn"
    assert serve(lm + "block07/ln1/rsqrt") == "attn"
    assert serve(lm + "block07/mlp/fc1/dot_general") == "mlp"
    assert serve(lm + "block07/add") == "mlp"
    assert serve(lm + "ln/rsqrt") == "head_sample"
    assert serve(lm + "embed.attend/dot_general") == "head_sample"
    assert serve("jit(_masked_step)/cond/branch_1_fun/reduce_max") == \
        "head_sample"
    assert serve(lm + "embed/jit(_take)/gather") == "embed"
    # an operation that only moves an argument carries the argument's
    # name, its quotes escaped as the HLO text prints them
    assert serve(r"cache[\'block07\'][\'attn\'][\'cached_k\']") == "kv_copy"
    assert serve(r"params[\'block00\'][\'attn\'][\'out\'][\'bias\']") == \
        "attn"
    assert serve(r"params[\'block00\'][\'mlp\'][\'fc1\'][\'kernel\']") == \
        "mlp"
    assert serve(r"params[\'embed\'][\'embedding\']") == "head_sample"
    assert serve(r"params[\'pos_embed\']") == "embed"
    assert serve("extra[3]") is None
    train = scope_time.scopes_for("train")
    assert train("jit(f)/tpunet_fwd_bwd/jvp(M)/x") == "fwd"
    assert train("jit(f)/tpunet_fwd_bwd/transpose(jvp(M))/x") == "bwd"
    assert train("jit(f)/tpunet_augment/resize") == "augment"
    assert train("jit(f)/convert_element_type") is None     # 'other'


def _read(metric, obs=None):
    spec = harness.load_json("benchmark", "layer_metrics", f"{metric}.json",
                             root=bench_tiny_root.REPO)
    reader = harness.load_reader(CELL, spec["reader"])
    return reader.read(obs or {"trace": None}, spec["params"])


SCOPE_METRICS = [
    "decode_device_ms.serve", "prefill_device_ms.serve",
    "decode_attn_ms.serve", "decode_kv_copy_ms.serve", "decode_mlp_ms.serve",
    "decode_head_sample_ms.serve", "scope_unattributed_pct.serve",
    "scope_unattributed_pct.train", "fwd_bwd_ms.train", "optimizer_ms.train",
    "augment_ms.train", "bwd_share_pct.train"]
OTHER_METRICS = [
    "prefill_rows_per_call.serve", "prefill_useful_tok_pct.serve",
    "decode_live_rows_pct.serve", "idle_unattributed_pct.train",
    "idle_unattributed_pct.serve"]


@pytest.mark.parametrize("metric", SCOPE_METRICS + OTHER_METRICS)
def test_every_new_reader_returns_none_without_a_trace(metric):
    scope_time.table.cache_clear()
    assert scope_time.xplane_path(["run.py", "--trace", "0"]) is None
    assert _read(metric) is None


@pytest.fixture
def traced_process(providers, monkeypatch, tmp_path):
    """This process as the traced run's child: ``--workdir`` on its
    command line, the recorded trace under it, the recorded step in the
    program's table."""
    os.makedirs(tmp_path / "trace" / "plugins")
    shutil.copy(XPLANE, tmp_path / "trace" / "plugins")
    monkeypatch.setattr(sys, "argv", ["run.py", "--stage", "program",
                                      "--workdir", str(tmp_path)])
    holder = _Holder({"jit_step": _probe_text()})
    device_time.register_programs(holder.program_texts)
    scope_time.table.cache_clear()
    yield holder
    scope_time.table.cache_clear()


@pytest.mark.parametrize("metric,low,high", [
    ("fwd_bwd_ms.train", 0.185, 0.187),
    ("optimizer_ms.train", 0.020, 0.023),
    ("augment_ms.train", 0.0, 0.0),
    ("bwd_share_pct.train", 0.0, 0.0),
    ("scope_unattributed_pct.train", 4.3, 4.6),     # the copy of x
    ("decode_device_ms.serve", None, None),         # no such program here
])
def test_scope_readers_on_the_recorded_trace(traced_process, metric, low,
                                             high):
    got = _read(metric)
    if low is None:
        assert got is None
    else:
        assert low <= got <= high
    _read(metric)
    assert traced_process.calls <= 2              # one table per family


def test_a_table_that_cannot_be_read_leaves_the_metric_out(
        traced_process, monkeypatch, capsys):
    monkeypatch.setattr(device_time, "program_texts", _raises)
    assert _read("fwd_bwd_ms.train") is None
    assert "not read" in capsys.readouterr().out


class _Engine:
    slots = 4

    class registry:
        @staticmethod
        def counter(name):
            return type("C", (), {"value": 9.0})

    @staticmethod
    def bucket_for(n):
        return 8 if n <= 8 else 32


class _Load:
    def __init__(self):
        prompts = [5, 7, 20, 6, 30, 8]
        self.requests = [{"prompt": [0] * n} for n in prompts]
        # (sent, queue_s): requests 0,1 share a call at t=1.0; 2 is the
        # other bucket at the same admission; 3 waits for t=2.0; 4 is
        # before the window; 5 never reached a slot
        stamps = [(0.90, 0.1), (0.95, 0.0501), (0.99, 0.0102), (1.5, 0.5),
                  (0.1, 0.2), (2.5, None)]
        self.records = [
            {"index": i, "sent": s, "queue_s": q,
             "token_t": [] if q is None else
             [s + q + 0.1 * k for k in range(1, 5)]}
            for i, (s, q) in enumerate(stamps)]


def _as_the_runner_calls(metric, trace=None):
    engine, load, t_open, t_close = _Engine(), _Load(), 0.5, 3.0  # noqa: F841
    return _read(metric, {"trace": trace})


def test_serve_window_counts_from_the_runners_own_records():
    assert _as_the_runner_calls("prefill_rows_per_call.serve") == \
        pytest.approx(4 / 3)
    assert _as_the_runner_calls("prefill_useful_tok_pct.serve") == \
        pytest.approx(100.0 * (5 + 7 + 20 + 6) / (4 * (8 + 32 + 8)))
    assert _as_the_runner_calls("decode_live_rows_pct.serve") is None
    spans = [("tpunet/serve_decode", 0.1 * k, 0.05) for k in range(4)] + [
        ("tpunet/serve_prefill", 1.0, 0.3)]
    trace = trace_reduce.Trace(device_ops={}, host_spans=spans)
    # decode tokens inside [0.5, 3.0): 3 each of requests 0-3, 3 of 4, 0 of 5
    assert _as_the_runner_calls("decode_live_rows_pct.serve", trace) == \
        pytest.approx(100.0 * 15 / (4 * 4))
    assert _as_the_runner_calls("prefill_rows_per_call.serve", trace) == \
        pytest.approx(4 / 3)


def test_idle_the_programs_spans_do_not_explain():
    ops = [("%a = f32[] add()", 0.0, 1.0), ("%b = f32[] add()", 2.0, 1.0),
           ("%c = f32[] add()", 3.5, 0.5), ("%d = f32[] add()", 8.0, 1.0)]
    spans = [("tpunet/data_wait", 0.9, 0.8),      # covers 0.7 of gap 1..2
             ("train", 2.9, 0.3),                 # covers 0.2 of gap 3..3.5
             ("tpunet/serve_decode", 4.0, 1.0)]   # covers 1 of gap 4..8
    trace = trace_reduce.Trace(device_ops={"/device:TPU:0": ops},
                               host_spans=spans)
    assert _read("idle_unattributed_pct.train", {"trace": trace}) == \
        pytest.approx(100.0 * 4.5 / 5.5)
    spans.append(("tpunet/serve_prefill", 3.9, 4.0))
    assert _read("idle_unattributed_pct.serve", {"trace": trace}) == \
        pytest.approx(100.0 * 0.5 / 5.5)
    assert _read("idle_unattributed_pct.serve",
                 {"trace": trace_reduce.Trace()}) is None
